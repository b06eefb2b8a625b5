#!/usr/bin/env python3
"""Turn chip runs' ``result.json`` into ``expected/<cell>/seed<k>.json``.

    python3 benchmarks/record_expected.py "PR 23 chip run" chiprun_out/bench/*/seed*-trace0/result.json

The record is the system's own output at epoch 0 — the raw ``reward/*_mean`` of
a seeded population on seeded weights — not a reference: no plain float32
reference of these models exists in the repository. A later run of the same
cell and seed has to land within two bfloat16 spacings of each value
(``drivers/es_train.py``). Only a benchmark PR writes these files; a file that
is there is never overwritten.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


def main(argv) -> int:
    origin, paths = argv[0], argv[1:]
    for path in paths:
        r = json.loads(Path(path).read_text())
        if r["device"]["platform"] != "tpu" or not r["correct"]:
            print(f"skipped {path}: not a correct chip run")
            continue
        out = BENCH_DIR / "expected" / r["cell"] / f"seed{r['seed']}.json"
        if out.exists():
            print(f"kept {out.relative_to(BENCH_DIR)}: already recorded")
            continue
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps({
            "cell": r["cell"], "seed": r["seed"], "recorded": origin,
            "device": {k: r["device"][k] for k in ("platform", "kind", "count")},
            "epoch0_reward_means": r["driver"]["epoch0_reward_means"],
        }, indent=1) + "\n")
        print(f"wrote {out.relative_to(BENCH_DIR)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
