"""Launch-layer tests: cost model, input specs, shardings, small-mesh dryrun."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.launch import hlo_cost as HC
from repro.launch.hlo_analysis import analytic_model_flops, peaks
from repro.launch.input_specs import SHAPES, SKIPS, input_specs, live_cells


def test_hlo_cost_scan_multiplier_exact():
    def f(x, w):
        def step(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(step, x, w)
        return y.sum()

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((12, 64, 64), jnp.float32)).compile()
    r = HC.analyse_text(c.as_text(), 1)
    expect = 12 * (2 * 64**3)
    assert abs(r["flops"] - expect) / expect < 0.05


def test_hlo_cost_nested_scan():
    def f(x, w):
        def outer(c, wi):
            def inner(ci, _):
                return ci @ wi, None
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None
        y, _ = jax.lax.scan(outer, x, w)
        return y.sum()

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32),
        jax.ShapeDtypeStruct((5, 32, 32), jnp.float32)).compile()
    r = HC.analyse_text(c.as_text(), 1)
    expect = 5 * 3 * 2 * 32**3
    assert abs(r["flops"] - expect) / expect < 0.1


def test_live_cells_count():
    cells = list(live_cells())
    assert len(cells) == 4 * len(ARCHS) - len(SKIPS) == 35
    for skip in SKIPS:
        assert skip not in cells


@pytest.mark.parametrize("arch", ARCHS)
def test_input_specs_no_allocation(arch):
    cfg = get_config(arch)
    for shape in SHAPES:
        if (arch, shape) in SKIPS:
            continue
        spec = input_specs(cfg, shape)
        leaves = jax.tree.leaves(
            {k: v for k, v in spec.items() if k.endswith("_spec")})
        assert leaves, (arch, shape)
        for leaf in leaves:
            assert isinstance(leaf, jax.ShapeDtypeStruct), (arch, shape, leaf)


def test_analytic_model_flops_attention_grows_with_seq():
    cfg = get_config("gemma2_2b")
    po = 6 * cfg.param_count()
    r4k = analytic_model_flops(cfg, "train", 256, 4096) / (po * 256 * 4096)
    r32k = analytic_model_flops(cfg, "train", 32, 32768) / (po * 32 * 32768)
    assert r4k > 1.0  # attention adds on top of 6ND
    assert r32k > r4k  # and its share grows with context (global layers)


def test_param_shardings_divisibility_guards():
    """Every generated sharding must divide its dim (hubert's 504-vocab head
    and mamba's 3352-wide in_proj exercise the fallbacks)."""
    os.environ.setdefault("XLA_FLAGS", "")
    from repro.launch.shardings import make_param_shardings
    from repro.models import build_model

    from jax.sharding import AxisType

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    for arch in ("hubert_xlarge", "mamba2_130m", "mixtral_8x22b"):
        cfg = get_config(arch)
        model = build_model(cfg)
        shape = jax.eval_shape(model.init, jax.random.key(0))
        sh = make_param_shardings(mesh, shape)
        assert jax.tree.structure(sh, is_leaf=lambda x: hasattr(x, "spec")) \
            .num_leaves > 0


@pytest.mark.slow
def test_dryrun_one_cell_subprocess():
    """Full 512-device lower+compile for one small cell in a subprocess."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = os.path.join(os.path.dirname(__file__), "..", "results",
                       "dryrun_test")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "mamba2_130m", "--shape", "long_500k",
         "--multi-pod", "--out-dir", out],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "[ok]" in proc.stdout


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")
