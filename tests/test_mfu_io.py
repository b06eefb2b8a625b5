"""Unit coverage for utils/mfu (peak lookup, cost-analysis FLOPs, the MFU
formula) and weights/io (shard merging, prefix stripping, wrapper unwrap)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


# -- mfu -------------------------------------------------------------------


def test_device_peak_flops_matches_on_exact_kind():
    from hyperscalees_t2i_tpu.utils import mfu

    class FakeDev:
        def __init__(self, kind, platform="tpu"):
            self.device_kind = kind
            self.platform = platform

    assert mfu.device_peak_flops(FakeDev("TPU v5 lite")) == 197e12
    assert mfu.device_peak_flops(FakeDev("TPU v5p")) == 459e12
    assert mfu.device_peak_flops(FakeDev("TPU v6e")) == 918e12
    # off the TPU nothing is measured: None, the gates stay unarmed
    assert mfu.device_peak_flops(FakeDev("cpu", "cpu")) is None
    assert mfu.device_peak_flops(FakeDev("NVIDIA H100", "gpu")) is None
    # a TPU the table does not name exactly is an error, never a v5p default
    # (the old substring table read any unknown "v5…" kind as 459 TFLOP/s)
    for kind in ("TPU v5 lite pod", "TPU v5x", "TPU7x", ""):
        with pytest.raises(ValueError, match="unknown TPU device_kind"):
            mfu.device_peak_flops(FakeDev(kind))


def test_hbm_tables_match_on_kind():
    """The roofline's second and third axes (utils/mfu): HBM bandwidth and
    capacity resolve by exact device_kind string or chip name, one table."""
    from hyperscalees_t2i_tpu.utils import mfu

    assert mfu.hbm_bw_for_kind("TPU v5 lite") == 819e9
    assert mfu.hbm_bw_for_kind("TPU v5p") == 2765e9
    assert mfu.hbm_bytes_for_kind("TPU v5e") == 16e9
    assert mfu.hbm_bytes_for_kind("v5e") == 16e9  # chip name (preflight --chip)
    assert mfu.hbm_bytes_for_kind("TPU v4") == 32e9
    assert mfu.hbm_bw_for_kind("NVIDIA H100") is None
    assert mfu.hbm_bw_for_kind("TPU v5p chip") is None  # no substring matching
    assert mfu.hbm_bytes_for_kind("") is None
    # every device_kind string resolves to a chip in the table
    assert set(mfu.DEVICE_KINDS.values()) <= set(mfu.CHIPS)

    class FakeDev:
        device_kind = "TPU v6e"
        platform = "tpu"

    assert mfu.device_hbm_bandwidth(FakeDev()) == 1640e9
    assert mfu.device_hbm_bytes(FakeDev()) == 32e9


def test_executable_flops_and_formula():
    from hyperscalees_t2i_tpu.utils.mfu import executable_flops, mfu

    @jax.jit
    def f(a, b):
        return a @ b

    x = jnp.ones((64, 64))
    compiled = f.lower(x, x).compile()
    fl = executable_flops(compiled)
    assert fl is not None and fl >= 2 * 64**3 * 0.9  # ~2*n^3 matmul FLOPs
    # formula: flops / (t * peak * n); CPU has no known peak → None
    assert mfu(fl, 1.0) is None or isinstance(mfu(fl, 1.0), float)
    assert mfu(None, 1.0) is None


# -- weights/io ------------------------------------------------------------


def test_strip_prefix_all_or_nothing():
    from hyperscalees_t2i_tpu.weights import strip_prefix

    sd = {"model.a": 1, "model.b": 2}
    assert strip_prefix(sd, "model") == {"a": 1, "b": 2}
    mixed = {"model.a": 1, "other.b": 2}
    assert strip_prefix(mixed, "model") == mixed  # non-uniform → untouched


def test_load_state_dict_merges_sharded_dir(tmp_path):
    torch = pytest.importorskip("torch")
    from hyperscalees_t2i_tpu.weights import load_state_dict

    d = tmp_path / "ckpt"
    d.mkdir()
    torch.save({"w1": torch.ones(2, 2)}, d / "part-00001.bin")
    torch.save({"w2": torch.zeros(3)}, d / "part-00002.bin")
    sd = load_state_dict(d)
    assert set(sd) == {"w1", "w2"}
    np.testing.assert_allclose(sd["w1"], np.ones((2, 2)))


def test_load_state_dict_unwraps_and_upcasts(tmp_path):
    torch = pytest.importorskip("torch")
    from hyperscalees_t2i_tpu.weights import load_state_dict

    path = tmp_path / "wrapped.pt"
    torch.save({"state_dict": {"w": torch.ones(2, dtype=torch.bfloat16)}}, path)
    sd = load_state_dict(path)
    assert sd["w"].dtype == np.float32  # numpy has no bf16 → upcast
    np.testing.assert_allclose(sd["w"], [1.0, 1.0])


def test_load_state_dict_empty_dir_raises(tmp_path):
    from hyperscalees_t2i_tpu.weights import load_state_dict

    with pytest.raises(FileNotFoundError, match="no checkpoint files"):
        load_state_dict(tmp_path)
