"""Stage marks: the ``evd_mark_<stage>`` kernels and ``evd.<stage>`` scopes
that name each stage of a solve on the device timeline.

A mark is an identity (bitwise), the marked programs compute bitwise what
the unmarked ones do, and a program compiled for a described TPU v5e holds
the marks in stage order, with the scopes in its instructions' ``op_name``.
The v5e compiles follow ``test_tpu_compile.py``: the topology is described
inside a fixture, never at import.
"""
import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.solver import EvdConfig, plan, solve_many, trace_count

plan_mod = importlib.import_module("repro.solver.plan")

VALUES_STAGES = ["begin", "first_stage", "bulge_chase", "bisection"]
VECTOR_STAGES = VALUES_STAGES + ["inverse_iteration", "backtransform_q2", "backtransform_q1"]


@pytest.mark.parametrize("stage", ["begin", "first_stage", "backtransform_q2"])
def test_stage_mark_returns_its_tile_bitwise(stage):
    tile = jax.random.normal(jax.random.key(1), (8, 128), jnp.float32)
    tile = tile.at[0, 0].set(jnp.nan).at[0, 1].set(-0.0)
    out = ops.stage_mark(tile, stage, interpret=True)
    assert out.dtype == tile.dtype
    np.testing.assert_array_equal(
        np.asarray(out).view(np.uint32), np.asarray(tile).view(np.uint32)
    )


@pytest.mark.parametrize("stage", ["first.stage", "bulge chase", ""])
def test_stage_names_keep_to_letters_digits_and_underscores(stage):
    with pytest.raises(ValueError):
        ops.stage_mark(jnp.zeros((8, 128), jnp.float32), stage, interpret=True)


def _unmarked(stage, outs):
    return outs


@pytest.mark.parametrize("program", ["eigvals", "eigh", "inverse_pth_root"])
def test_marked_programs_compute_bitwise_what_unmarked_ones_do(program, monkeypatch):
    n = 256
    rng = np.random.default_rng(13)
    G = rng.standard_normal((n, n)).astype(np.float32)
    A = jnp.asarray(G @ G.T / n + 0.1 * np.eye(n, dtype=np.float32))
    pl = plan(n, jnp.float32)
    run = {
        "eigvals": lambda: pl.eigvals(A),
        "eigh": lambda: pl(A),
        "inverse_pth_root": lambda: pl.inverse_pth_root(A, 4),
    }[program]

    jax.clear_caches()
    marked = jax.device_get(run())
    # No barrier and no kernel: the program as it was before the marks.
    monkeypatch.setattr(plan_mod, "_end_stage", _unmarked)
    jax.clear_caches()
    unmarked = jax.device_get(run())
    monkeypatch.undo()
    jax.clear_caches()
    for a, b in zip(jax.tree_util.tree_leaves(marked), jax.tree_util.tree_leaves(unmarked)):
        np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))


def test_solve_many_runs_marked_without_retracing():
    n, batch = 64, 3
    rng = np.random.default_rng(5)
    G = rng.standard_normal((batch, n, n)).astype(np.float32)
    S = jnp.asarray(np.einsum("bij,bkj->bik", G, G) / n + 0.1 * np.eye(n, dtype=np.float32))
    cfg = EvdConfig(b=4, nb=16)
    X = solve_many(S, cfg, op="inverse_pth_root", p=2)
    before = trace_count()
    X2 = solve_many(S, cfg, op="inverse_pth_root", p=2)
    assert trace_count() == before
    np.testing.assert_array_equal(np.asarray(X), np.asarray(X2))
    S64 = np.asarray(S, np.float64)
    for i in range(batch):
        w, V = np.linalg.eigh(S64[i])
        ref = (V * w ** -0.5) @ V.T
        assert np.linalg.norm(np.asarray(X[i]) - ref) / np.linalg.norm(ref) < 1e-4


@pytest.mark.parametrize("method", ["jacobi", "direct"])
def test_one_stage_methods_mark_begin_and_their_end(method, monkeypatch):
    n = 32
    A = jax.ShapeDtypeStruct((n, n), jnp.float32)
    calls = []

    def spy(stage, outs):
        calls.append(stage)
        return outs

    pl = plan(n, jnp.float32, EvdConfig(method=method))
    monkeypatch.setattr(plan_mod, "_end_stage", spy)
    jax.clear_caches()
    jax.jit(pl).lower(A)
    monkeypatch.undo()
    jax.clear_caches()
    assert calls == ["begin", method]


# ------------------------------------------------------- compiled for a v5e
@pytest.fixture(scope="module")
def v5e_programs():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back without the chip;
    # keep it out of the persistent cache.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    mp = pytest.MonkeyPatch()
    # The plan and the kernels must see a TPU: its tables, no interpreter.
    mp.setattr("repro.backend.probe.platform", lambda: "tpu")
    try:
        n = 512
        pl = plan(n, jnp.float32, EvdConfig(backend="pallas"))
        spec = jax.ShapeDtypeStruct(
            (n, n), jnp.float32, sharding=SingleDeviceSharding(topo.devices[0])
        )
        yield {
            "eigvals": jax.jit(pl.eigvals).lower(spec).compile().as_text(),
            "eigh": jax.jit(pl).lower(spec).compile().as_text(),
        }
    finally:
        mp.undo()
        jax.config.update("jax_enable_compilation_cache", prev)


def _marks(text):
    entry = text[text.index("\nENTRY"):]
    return re.findall(r"^\s*(?:ROOT )?%evd_mark_(\w+?)(?:\.\d+)* = ", entry, re.M)


@pytest.mark.parametrize("program,stages", [("eigvals", VALUES_STAGES), ("eigh", VECTOR_STAGES)])
def test_v5e_program_holds_the_marks_in_stage_order(v5e_programs, program, stages):
    text = v5e_programs[program]
    assert _marks(text) == stages
    for s in stages:
        line = next(l for l in text.splitlines() if f"%evd_mark_{s}" in l.split("=")[0])
        assert 'custom_call_target="tpu_custom_call"' in line


@pytest.mark.parametrize("program,stages", [("eigvals", VALUES_STAGES), ("eigh", VECTOR_STAGES)])
def test_v5e_program_names_the_stages_in_op_name(v5e_programs, program, stages):
    scopes = set(re.findall(r'op_name="[^"]*?evd\.(\w+)', v5e_programs[program]))
    assert {"first_stage", "bulge_chase", "bisection"} <= scopes
    assert scopes <= set(stages) - {"begin"}
