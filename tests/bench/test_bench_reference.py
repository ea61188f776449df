"""The correctness comparison at smoke size on the CPU.

A run of the harness (everything but its look for a chip) serves a
small backlog through the engine's paged Pallas path (interpret mode
here) and holds the served tokens to the float32 reference.  Clean, it
passes; with the timed path broken underneath it, ``correct`` comes out
false; and the control, the reference itself computed in float8, fails
the same comparison.

Sizes: 2 layers of width 128, 4 query and 2 KV heads of 32, vocabulary
512, float32 (the smoke preset's precision).  The engine's decode kernel
still reads K/V rounded through bfloat16, so near-ties flip: clean runs
read gaps of 0 to 0.0042 over three seeds, the float8 control 0.48 to
0.64.  ``LIMIT`` sits a factor of ten from each (readings in PERF.md).
"""

import copy

import jax
import jax.numpy as jnp
import pytest

from bench import check, run
from repro.models import model as model_lib

LIMIT = 0.05
MIX = {"kind": "backlog", "queue_per_slot": 2, "cycle": 12,
       "prompt": {"median": 40, "sigma": 0.8, "min": 8, "max": 100},
       "output": {"median": 16, "sigma": 0.7, "min": 6, "max": 40},
       "check_requests": 12}


def _config(name):
    cfg = run.load_json(run.ROOT / "bench" / "configs" / f"{name}.json")
    cfg.update({"hidden_size": 128, "intermediate_size": 256,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 32, "num_hidden_layers": 2, "vocab_size": 512,
                "torch_dtype": "float32"})
    cfg["engine"].update({"slots": 4, "max_len": 256, "prefill_chunk": 32})
    return cfg


def _serve(config="qwen3-8b", seconds=2.0, seed=2 ** 33 + 7):
    """One run; returns its result line and what the check compared."""
    seen = {}
    served_gap = check.served_gap

    def spy(ref, c, params, reqs):
        seen.update(ref=ref, c=c, params=params, reqs=reqs)
        return served_gap(ref, c, params, reqs)

    w = {"name": "smoke", "config": config, "traffic": "smoke", "chips": 1}
    files = {"config": _config(config), "mix": MIX,
             "cell": {"limits": {"max_logit_gap": LIMIT}}}
    bench = copy.deepcopy(run.benchmark())
    for m in bench["end_to_end"]:
        m.pop("workloads", None)
    check.served_gap = spy
    try:
        out = run.run_cell(w, files, seed, seconds, False, jax.devices()[:1],
                           bench, log=lambda m: None)
    finally:
        check.served_gap = served_gap
    return out, seen


@pytest.fixture(scope="module")
def clean():
    return _serve()


@pytest.mark.parametrize("config", ["qwen3-8b", "qwen2-7b"])
def test_engine_agrees_with_the_reference(config, clean):
    out, seen = clean if config == "qwen3-8b" else _serve(config)
    assert out["correct"] is True
    gap = out["checks"]["max_logit_gap"]
    assert 0.0 <= gap["value"] <= LIMIT and gap["limit"] == LIMIT
    assert sum(len(r.out) for r in seen["reqs"]) >= 50
    assert list(out)[-1] == "checks"
    assert out["metrics"]["output_tok_s"]["value"] > 0


def test_the_control_fails_the_comparison(clean):
    _, seen = clean
    got = check.control_gap(seen["ref"], seen["c"], seen["params"],
                            seen["reqs"])
    assert got["tokens"] == sum(len(r.out) for r in seen["reqs"])
    assert not check.verdict(got, {"max_logit_gap": LIMIT})["correct"]


def _state_unchanged(real):
    def fault(cfg, params, pool, cache, *args, **kw):
        _, cache2, toks, valid, final = real(cfg, params, pool, cache,
                                             *args, **kw)
        return pool, cache2, toks, valid, final     # K/V never written
    return fault


def _half_batch(real):
    def fault(*args, **kw):
        pool, cache, toks, valid, final = real(*args, **kw)
        h = toks.shape[0] // 2                       # rows h.. copy rows ..h
        toks = jnp.concatenate([toks[:h], toks[:toks.shape[0] - h]])
        final = jnp.concatenate([final[:h], final[:final.shape[0] - h]])
        return pool, cache, toks, valid, final
    return fault


def _token_altered(real):
    def fault(cfg, *args, **kw):
        pool, cache, toks, valid, final = real(cfg, *args, **kw)
        toks = toks.at[0, 0].set((toks[0, 0] + 1) % cfg.vocab_size)
        return pool, cache, toks, valid, final
    return fault


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_a_broken_timed_path_is_not_correct(plant, monkeypatch):
    monkeypatch.setattr(model_lib, "decode_steps_paged",
                        plant(model_lib.decode_steps_paged))
    out, _ = _serve(seed=2 ** 33 + 11)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > LIMIT
