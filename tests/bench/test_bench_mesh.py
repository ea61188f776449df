"""The harness on a four-device mesh, on the CPU: four forced host
devices in a subprocess (the test process keeps its single CPU device), at
smoke size.

* ``make_weights`` on mesh (1, 4) gives the same values as on (1, 1),
  each leaf placed as the engine keeps it, so the engine moves nothing;
* a run of the harness on mesh (1, 4) (everything but its look for a
  chip) serves through the head-sharded paged Pallas path (interpret
  mode here) and passes the float32 check against the sharded weights;
  the float8 control fails it, and so does the timed path with each
  fault that a tensor-parallel cell can have, the exchange between
  chips among them.

Sizes: 2 layers of width 128, 8 query and 4 KV heads of 32 (one KV head
a chip), feed-forward 256, vocabulary 512, float32."""

import json
import os
import subprocess
import sys

import pytest

from bench import run

ROOT = run.ROOT
LIMIT = 0.05           # as tests/bench/test_bench_reference.py, same sizes

_WEIGHTS = r"""
import json, sys
import jax, numpy as np
from bench import check, run
from repro.distributed import sharding
from repro.runtime.serve_loop import make_serve_engine

cfg = json.loads(sys.argv[1])
ref = check.reference_module(cfg)
dims = ref.dims(cfg)
mcfg = run.model_config(cfg)
seed = 2 ** 33 + 5
one = run.make_weights(ref, dims, mcfg, seed, (1, 1))
four = run.make_weights(ref, dims, mcfg, seed, (1, 4))
flat1 = jax.tree_util.tree_flatten_with_path(one)[0]
flat4 = jax.tree_util.tree_flatten_with_path(four)[0]
mesh = sharding.serve_mesh(1, 4)
want = sharding.param_shardings(four, mesh)
engine = make_serve_engine(mcfg, four, mesh_shape=(1, 4), **cfg["engine"])
out = {"one_devices": sorted({d.id for _, x in flat1 for d in x.devices()}),
       "leaves": []}
for (path, a), (_, b), s, e in zip(flat1, flat4, jax.tree.leaves(want),
                                   jax.tree.leaves(engine.params)):
    out["leaves"].append({
        "path": jax.tree_util.keystr(path),
        "same_values": bool(np.array_equal(np.asarray(a), np.asarray(b))),
        "dtype": str(b.dtype) == str(a.dtype),
        "engine_sharding": b.sharding.is_equivalent_to(s, b.ndim),
        "split": len({str(sh.index) for sh in b.addressable_shards}) > 1,
        "kept_by_engine": e is b})
print(json.dumps(out))
"""

_RUN = r"""
import json, sys
import jax, jax.numpy as jnp
from bench import check, run
from repro.models import model as model_lib
from test_bench_reference import _half_batch, _state_unchanged, _token_altered

cfg, mix, limit = json.loads(sys.argv[1]), json.loads(sys.argv[2]), float(sys.argv[3])
MP = cfg["mesh"][1]


def exchange_left_out(real):
    # the row-split projections' partial sums are never added across
    # chips: each keeps its own, here the first chip's
    def fault(cfg, params, *args, **kw):
        layers = dict(params["layers"])
        for name in ("attn_wo", "ffn_w_down"):
            w = layers[name]
            keep = jnp.arange(w.shape[1]) < w.shape[1] // MP
            layers[name] = jnp.where(keep[None, :, None], w, 0)
        return real(cfg, dict(params, layers=layers), *args, **kw)
    return fault


def serve(seed, plant=None):
    seen = {}
    served_gap, real = check.served_gap, model_lib.decode_steps_paged

    def spy(ref, c, params, reqs):
        seen.update(ref=ref, c=c, params=params, reqs=reqs)
        return served_gap(ref, c, params, reqs)

    w = {"name": "smoke-tp4", "config": cfg["name"], "traffic": "smoke",
         "chips": 4}
    files = {"config": cfg, "mix": mix,
             "cell": {"limits": {"max_logit_gap": limit}}}
    bench = run.benchmark()
    for m in bench["end_to_end"]:
        m.pop("workloads", None)
    check.served_gap = spy
    if plant is not None:
        model_lib.decode_steps_paged = plant(real)
    try:
        out = run.run_cell(w, files, seed, 2.0, False, jax.devices()[:4],
                           bench, log=lambda m: None)
    finally:
        check.served_gap, model_lib.decode_steps_paged = served_gap, real
    return out, seen


out, seen = serve(2 ** 33 + 7)
ctl = check.control_gap(seen["ref"], seen["c"], seen["params"], seen["reqs"])
result = {
    "clean": out,
    "tokens": sum(len(r.out) for r in seen["reqs"]),
    "weights_split": all(len(x.sharding.device_set) == 4
                         for x in jax.tree.leaves(seen["params"])),
    "control": ctl,
    "control_correct": check.verdict(ctl, {"max_logit_gap": limit})["correct"],
}
for plant in (exchange_left_out, _state_unchanged, _half_batch,
              _token_altered):
    result[plant.__name__.lstrip("_")] = serve(2 ** 33 + 11, plant)[0]
print(json.dumps(result))
"""


def _config():
    cfg = run.load_json(ROOT / "bench" / "configs" / "qwen3-8b-tp4.json")
    cfg.update({"hidden_size": 128, "intermediate_size": 256,
                "num_attention_heads": 8, "num_key_value_heads": 4,
                "head_dim": 32, "num_hidden_layers": 2, "vocab_size": 512,
                "torch_dtype": "float32"})
    cfg["engine"].update({"slots": 4, "max_len": 256, "prefill_chunk": 32})
    return cfg


def _four_devices(script, *args, timeout=900):
    p = subprocess.run(
        [sys.executable, "-c", script, *args], capture_output=True,
        text=True, timeout=timeout, cwd=str(ROOT),
        env={**os.environ,
             "PYTHONPATH": os.pathsep.join(["src", ".", "tests/bench"]),
             "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_weights_on_the_mesh_are_the_one_device_weights_placed_as_served():
    cfg = _config()
    got = _four_devices(_WEIGHTS, json.dumps(cfg))
    assert got["one_devices"] == [0]
    leaves = got["leaves"]
    ref = run.check.reference_module(cfg)
    assert len(leaves) == len(ref.shapes(ref.dims(cfg)))
    for leaf in leaves:
        assert leaf["same_values"] and leaf["dtype"], leaf
        assert leaf["engine_sharding"] and leaf["kept_by_engine"], leaf
    # heads, MLP hidden and vocabulary are split; norms are whole
    split = {x["path"] for x in leaves if x["split"]}
    assert "['layers']['attn_wk']" in split and "['embed']" in split
    assert "['layers']['ffn_w_down']" in split and "['lm_head']" in split
    assert "['layers']['ln1']" not in split


@pytest.fixture(scope="module")
def mesh_runs():
    mix = run.load_json(ROOT / "bench" / "traffic" / "decode-backlog.json")
    mix.update({"cycle": 12, "check_requests": 12,
                "prompt": {"median": 40, "sigma": 0.8, "min": 8, "max": 100},
                "output": {"median": 16, "sigma": 0.7, "min": 6, "max": 40}})
    return _four_devices(_RUN, json.dumps(_config()), json.dumps(mix),
                         str(LIMIT), timeout=1200)


def test_the_mesh_run_agrees_with_the_reference(mesh_runs):
    out = mesh_runs["clean"]
    assert out["correct"] is True
    assert out["device"]["count"] == 4
    gap = out["checks"]["max_logit_gap"]
    assert 0.0 <= gap["value"] <= LIMIT and gap["limit"] == LIMIT
    assert mesh_runs["tokens"] >= 50
    assert mesh_runs["weights_split"]
    assert out["metrics"]["output_tok_s"]["value"] > 0


def test_the_control_fails_on_the_mesh(mesh_runs):
    assert mesh_runs["control"]["tokens"] == mesh_runs["tokens"]
    assert mesh_runs["control_correct"] is False


@pytest.mark.parametrize("plant", ["exchange_left_out", "state_unchanged",
                                   "half_batch", "token_altered"])
def test_a_broken_timed_path_on_the_mesh_is_not_correct(plant, mesh_runs):
    out = mesh_runs[plant]
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > LIMIT
