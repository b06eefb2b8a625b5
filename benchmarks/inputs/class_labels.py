"""Class names for VAR's reward text, written to a ``--labels_path`` file so
that set-up makes no network lookup (``load_class_names`` tries one for the
1000-class table). The names are ``class_<i>`` placeholders: with seeded
reward towers the text only has to be distinct per class."""

from __future__ import annotations

from pathlib import Path
from typing import List


def make(spec: dict, model: dict, seed: int, out_dir: Path, bench_dir: Path) -> List[str]:
    n = int(spec.get("num_classes", model["transformer"]["num_classes"]))
    path = out_dir / "labels.txt"
    path.write_text("".join(f"class_{i}\n" for i in range(n)))
    return [spec["flag"], str(path)]
