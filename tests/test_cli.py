"""CLI surface tests: parser, backend construction for every family, tiny
reward tower build (the unifed_es.py-equivalent layer, SURVEY.md L4)."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from hyperscalees_t2i_tpu.train.cli import (
    build_backend,
    build_parser,
    build_reward_fn,
    str2bool,
    train_config,
)


def parse(extra):
    return build_parser().parse_args(extra)


def test_str2bool():
    assert str2bool("true") and str2bool("1") and str2bool("Y")
    assert not str2bool("false") and not str2bool("0")
    with pytest.raises(Exception):
        str2bool("maybe")


@pytest.mark.parametrize(
    "backend", ["sana_one_step", "sana_pipeline", "var", "zimage", "infinity"]
)
def test_build_backend_tiny(backend, tmp_path):
    prompts = tmp_path / "p.txt"
    prompts.write_text("a\nb\nc\n")
    args = parse(
        ["--backend", backend, "--model_scale", "tiny", "--prompts_txt", str(prompts),
         "--lora_r", "2", "--lora_alpha", "4"]
    )
    b = build_backend(args)
    b.setup()
    assert b.num_items >= 1
    theta = b.init_theta(jax.random.PRNGKey(0))
    info = b.step_info(0, 1, 1)
    imgs = b.generate(theta, jnp.asarray(info.flat_ids, jnp.int32), jax.random.PRNGKey(1))
    assert imgs.ndim == 4 and imgs.shape[-1] == 3


def test_infinity_variant_and_pn_flags():
    args = parse(["--backend", "infinity", "--infinity_variant", "layer12", "--pn", "0.06M"])
    b = build_backend(args)
    assert b.cfg.model.depth == 12
    assert b.cfg.model.patch_nums == (1, 2, 3, 4, 5, 6, 8, 10, 13, 16)
    assert b.cfg.model.vq.patch_nums == b.cfg.model.patch_nums


def test_reward_fn_tiny(tmp_path):
    prompts = tmp_path / "p.txt"
    prompts.write_text("a red square\n")
    args = parse(["--backend", "sana_one_step", "--model_scale", "tiny",
                  "--prompts_txt", str(prompts)])
    b = build_backend(args)
    b.setup()
    rf = build_reward_fn(args, b)
    imgs = jnp.zeros((2, 8, 8, 3))
    out = rf(imgs, jnp.asarray([0, 0], jnp.int32))
    assert "combined" in out and out["combined"].shape == (2,)


# ---------------------------------------------------------------------------
# the benchmark's frozen files meet the program at this parser
# ---------------------------------------------------------------------------

ROOT = Path(__file__).resolve().parents[1]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_flags_parse(cell, tmp_path):
    """Every flag a cell of ``BENCHMARK.json`` passes — its configuration's,
    its traffic's, its generated inputs' (made at the rehearsal's size), the
    harness's own — goes through the parser into a ``TrainConfig``. No PR but
    a ``benchmark`` one may edit those files, so a flag the program drops has
    to go on parsing (``--pop_fuse true`` is the first)."""
    import importlib

    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    work = next(w for w in manifest["workloads"] if w["name"] == cell)
    entry = next(c for c in manifest["configs"] if c["name"] == work["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads((ROOT / "benchmarks" / "traffic" / f"{work['traffic']}.json").read_text())
    flags = {**config["flags"], **traffic["flags"]}
    spec = {**config["inputs"], **config["rehearse"].get("inputs", {})}
    gen = importlib.import_module(f"benchmarks.inputs.{spec['kind']}")
    made = gen.make(spec, config["model"], 0, tmp_path, ROOT / "benchmarks")
    flags.update(zip(made[::2], made[1::2]))
    flags.update({"--seed": "0", "--resume": "false", "--save_every": "0",
                  "--run_dir": str(tmp_path), "--run_name": "run",
                  "--num_epochs": "1000000", "--trace": "true"})
    args = parse([x for kv in flags.items() for x in kv])
    tc = train_config(args)
    assert args.backend == flags["--backend"] and args.model_scale == "full"
    assert (tc.pop_size, tc.prompts_per_gen, tc.member_batch) == tuple(
        int(traffic["flags"][f]) for f in ("--pop_size", "--prompts_per_gen", "--member_batch"))
    assert (tc.base_quant, tc.noise_dtype, tc.tower_dtype) == ("int8", "bfloat16", "bfloat16")
    assert (tc.remat, tc.reward_tile) == (flags["--remat"], int(flags["--reward_tile"]))
    assert tc.trace and not tc.resume and tc.seed == 0
    assert flags["--pop_fuse"] == "true" and not hasattr(tc, "pop_fuse")


def test_pop_fuse_false_is_refused_by_name(capsys):
    """The flag is parse-only: ``true`` is what the program does; ``false``
    asks for the removed member path and must not silently run the other."""
    assert parse(["--backend", "var", "--pop_fuse", "true"]).pop_fuse is True
    with pytest.raises(SystemExit) as e:
        parse(["--backend", "var", "--pop_fuse", "false"])
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "--pop_fuse" in err and "removed" in err
    assert "--pop_fuse" not in build_parser().format_help()
