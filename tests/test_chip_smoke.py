"""chip_smoke.py's logic at smoke size on the CPU, and the pieces the
chip run depends on: parameters made in place, the compile-cache rule.

The script's own ``main`` refuses any backend but a TPU; its phase
function is backend-agnostic, so the engine path, the teacher-forced
check and the reported fields are exercised here at no chip cost.
"""

import importlib.util
import pathlib
import sys

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

from repro.configs import get_config
from repro.distributed import sharding as sharding_lib
from repro.launch import compile_cache
from repro.models import model
from repro.runtime.serve_loop import ContinuousBatchingEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]
CFG = get_config("qwen3-8b").reduced()
SMALL = dict(slots=4, max_len=128, prefill_chunk=32, block_size=16)


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cs():
    return _load_chip_smoke()


@pytest.fixture(scope="module")
def params():
    return model.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def phase(cs, params):
    traffic = cs.Traffic(4, (20, 70), (5, 9, 13))
    return traffic, cs.serve_phase(CFG, params, traffic=traffic, **SMALL)


def test_phase_serves_and_passes_the_reference_check(cs, phase):
    traffic, r = phase
    assert r["requests"] == 2 * traffic.n
    assert r["served_per_replica"] == [2 * traffic.n]
    assert r["checked_tokens"] > 0
    assert 0.0 <= r["max_logit_gap"] <= cs.LOGIT_TOL
    assert r["argmax_agreement"] >= cs.MIN_AGREEMENT
    assert r["top2_spacing_median"] > 0.0
    assert 0.0 <= r["near_tie_share"] <= 1.0
    assert r["compiles"] > 0 and r["compile_s"] > 0.0
    # both passes have the same shapes: the second compiles nothing
    assert r["steady_compiles"] == 0
    assert r["steady_tok_per_s"] > 0.0


def test_report_prints_every_field(cs, phase, capsys):
    cs._report("cpu", phase[1])
    out = capsys.readouterr().out
    for needle in ("requests served", "compilations", "smoke reading",
                   "teacher-forced check passed", "max logit gap",
                   "top-2 spacing median"):
        assert needle in out


def test_reference_check_rejects_a_wrong_token(cs, params):
    """One generated token replaced by a token the model does not favour
    fails the check; the same request served cleanly passes it."""
    traffic = cs.Traffic(1, (20, 20), (9,))
    eng = ContinuousBatchingEngine(CFG, params, kv_layout="paged", **{
        k: v for k, v in SMALL.items() if k != "prefill_chunk"})
    (req,) = traffic.requests(CFG.vocab_size, seed=3)
    eng.submit(req)
    eng.run()
    cs.check_against_reference(CFG, params, [req], SMALL["max_len"])
    seq = np.concatenate([req.prompt, req.out]).astype(np.int32)
    logits = model.forward(CFG, params, {"tokens": seq[None]})[0]
    req.out[0] = int(np.argmin(np.asarray(logits[len(req.prompt) - 1])))
    with pytest.raises(cs.SmokeFailure, match="below the reference"):
        cs.check_against_reference(CFG, params, [req], SMALL["max_len"])


def test_reference_check_floors_argmax_agreement(cs, params, monkeypatch):
    """Tokens that are each the reference's runner-up pass any loose
    tolerance but fail the agreement floor."""
    monkeypatch.setattr(cs, "LOGIT_TOL", float("inf"))
    (req,) = cs.Traffic(1, (20, 20), (9,)).requests(CFG.vocab_size, seed=3)
    for _ in range(req.max_new_tokens):
        seq = np.concatenate([req.prompt, np.asarray(req.out, np.int32)])
        logits = model.forward(CFG, params, {"tokens": seq[None]})[0, -1]
        req.out.append(int(np.argsort(np.asarray(logits))[-2]))
    with pytest.raises(cs.SmokeFailure, match="reference's argmax"):
        cs.check_against_reference(CFG, params, [req], SMALL["max_len"])


def _zero_second_page(eng, i):
    """A zeroed KV page: the slot's second prompt page loses its K/V."""
    pid = eng.slots[i].pages[1]
    eng.page_pool = {k: a.at[:, pid].set(0) for k, a in eng.page_pool.items()}


def _block_table_off_by_one(eng, i):
    """A block-table entry one page off: column 0 reads page 1."""
    eng.cache = dict(eng.cache, bt=eng.cache["bt"].at[i, 0].set(
        eng.slots[i].pages[1]))


def _serve_planted(cs, params, plant):
    """Serve one request; once its prefill is done, ``plant`` (if any)
    corrupts its KV so that every later decode step reads the fault."""
    eng = ContinuousBatchingEngine(CFG, params, kv_layout="paged",
                                   decode_horizon=cs.DECODE_HORIZON, **SMALL)
    (req,) = cs.Traffic(1, (60, 60), (13,)).requests(CFG.vocab_size, seed=0)
    eng.submit(req)
    planted = plant is None
    while eng.step():
        for i, s in enumerate(eng.slots):
            if not planted and s.req is req and not s.prefilling:
                plant(eng, i)
                planted = True
    assert planted and len(req.out) == 13
    return req


@pytest.mark.parametrize("plant", [_zero_second_page, _block_table_off_by_one],
                         ids=["zeroed_page", "block_table_off_by_one"])
def test_reference_check_catches_a_planted_kv_fault(cs, params, plant):
    """A realistic engine fault fails the check, which the same request
    served without it passes."""
    clean = _serve_planted(cs, params, None)
    cs.check_against_reference(CFG, params, [clean], SMALL["max_len"])
    faulty = _serve_planted(cs, params, plant)
    with pytest.raises(cs.SmokeFailure, match="below the reference"):
        cs.check_against_reference(CFG, params, [faulty], SMALL["max_len"])


def test_main_refuses_the_cpu(cs, capsys):
    assert cs.main([]) == 1
    captured = capsys.readouterr()
    assert captured.out == ""          # no result line of any kind
    assert "no TPU" in captured.err


def test_traffic_shapes_follow_the_seed_alone(cs):
    t = cs.Traffic(5, (10, 40), (5, 9))
    a = t.requests(512, seed=7)
    b = t.requests(512, seed=7, rid0=5)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_init_params_on_a_mesh_is_placed_and_equal():
    mesh = sharding_lib.serve_mesh(1, 1)
    placed = model.init_params(CFG, jax.random.PRNGKey(0), mesh=mesh)
    plain = model.init_params(CFG, jax.random.PRNGKey(0))
    specs = sharding_lib.param_specs(plain, mesh)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(
        x, jax.sharding.PartitionSpec))
    for leaf, spec, ref in zip(jax.tree.leaves(placed), flat_s,
                               jax.tree.leaves(plain)):
        assert leaf.sharding.is_equivalent_to(NamedSharding(mesh, spec),
                                              leaf.ndim)
        assert leaf.dtype == np.dtype(CFG.dtype)
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(ref))


def test_engine_keeps_parameters_already_in_place(params):
    """A replica on the device that already holds the parameters must
    not copy them (a second 12-layer copy would not fit one chip)."""
    eng = ContinuousBatchingEngine(CFG, params, slots=2, max_len=32,
                                   kv_layout="paged",
                                   mesh_devices=jax.devices()[:1])
    assert eng.mesh is not None
    for mine, theirs in zip(jax.tree.leaves(eng.params),
                            jax.tree.leaves(params)):
        assert mine is theirs
    for leaf in jax.tree.leaves(eng.page_pool):
        assert isinstance(leaf.sharding, NamedSharding)


def test_compile_cache_leaves_a_set_directory_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(
        monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
