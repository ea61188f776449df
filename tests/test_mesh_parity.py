"""Serve mesh (dp x mp): sharding specs pinned against the REAL param
tree, and token-exact parity of the sharded engine vs the single-device
baseline.

Two halves with different device needs:

* Spec tests run against fake meshes (no devices touched) — always on,
  part of tier-1.
* Engine parity tests need multiple host devices; under plain tier-1
  (one CPU device) they skip.  Run them with

      XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
          PYTHONPATH=src python -m pytest tests/test_mesh_parity.py

  which is exactly what the non-blocking ``mesh-parity`` CI job does.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import ARCHS
from repro.core import bucket_label, occupancy_bucket, shard_bucket
from repro.distributed import sharding as shardlib
from repro.models import model
from repro.runtime.serve_loop import (
    ContinuousBatchingEngine, EngineReplicaGroup, Request, make_serve_engine)

CFG = ARCHS["qwen3-8b"].reduced()   # L=2 d=128 Hq=4 Hkv=2 hd=32 ff=256 V=512


def fake_mesh(shape=(1, 2), axes=("dp", "mp")):
    """Mesh over fake device objects — spec logic never touches devices."""
    class Dev:
        def __init__(self, i):
            self.id = i

        def __repr__(self):
            return f"D{self.id}"
    n = int(np.prod(shape))
    return Mesh(np.array([Dev(i) for i in range(n)],
                         dtype=object).reshape(shape), axes)


def abstract_params():
    """The actual transformer param tree (shapes only, no init cost)."""
    return jax.eval_shape(lambda k: model.init_params(CFG, k),
                          jax.random.PRNGKey(0))


class TestServeParamSpecs:
    """spec_for / param_specs against the real qwen3 tree on ('dp','mp')."""

    MESH = fake_mesh((2, 2))

    def test_attention_and_mlp_shard_on_mp(self):
        specs = shardlib.param_specs(abstract_params(), self.MESH)
        lay = specs["layers"]
        # head-dim outputs and ffn hidden shard on mp; their contracting
        # counterparts shard the OTHER dim so matmuls stay local
        assert lay["attn_wq"] == P(None, None, "mp")
        assert lay["attn_wk"] == P(None, None, "mp")
        assert lay["attn_wv"] == P(None, None, "mp")
        assert lay["attn_wo"] == P(None, "mp", None)
        assert lay["ffn_w_up"] == P(None, None, "mp")
        assert lay["ffn_w_gate"] == P(None, None, "mp")
        assert lay["ffn_w_down"] == P(None, "mp", None)

    def test_norms_replicated(self):
        specs = shardlib.param_specs(abstract_params(), self.MESH)
        for key in ("ln1", "ln2", "attn_q_norm", "attn_k_norm"):
            assert all(a is None for a in specs["layers"][key]), key
        assert all(a is None for a in specs["final_norm"])

    def test_dp_never_appears_in_param_specs(self):
        """dp is replica parallelism: every replica holds a FULL param
        copy, so no param spec may reference the dp axis (the training
        mesh's fsdp axis is 'data', deliberately not 'dp')."""
        flat = jax.tree_util.tree_leaves(
            shardlib.param_specs(abstract_params(), self.MESH),
            is_leaf=lambda x: isinstance(x, P))
        for spec in flat:
            assert "dp" not in [a for a in spec if a is not None]

    def test_every_sharded_dim_divides(self):
        """The divisibility contract spec_for promises, checked leaf by
        leaf on the real tree (this is what device_put would enforce)."""
        params = abstract_params()
        specs = shardlib.param_specs(params, self.MESH)
        sizes = shardlib.axis_sizes(self.MESH)
        flat_p = jax.tree_util.tree_leaves(params)
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        for leaf, spec in zip(flat_p, flat_s):
            assert len(spec) <= leaf.ndim
            for i, axis in enumerate(spec):
                if axis is not None:
                    assert leaf.shape[i] % sizes[axis] == 0, (leaf.shape, spec)

    def test_mp_indivisible_falls_back(self):
        """mp=3 divides nothing in the reduced tree cleanly at the ffn
        hidden?  256 % 3 != 0 -> the candidate ladder must land on a
        legal tail, never an illegal shard."""
        mesh = fake_mesh((1, 3))
        specs = shardlib.param_specs(abstract_params(), mesh)
        sizes = shardlib.axis_sizes(mesh)
        flat_p = jax.tree_util.tree_leaves(abstract_params())
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        for leaf, spec in zip(flat_p, flat_s):
            for i, axis in enumerate(spec):
                if axis is not None:
                    assert leaf.shape[i] % sizes[axis] == 0

    def test_fit_spec_trims_indivisible_serve_axes(self):
        mesh = fake_mesh((2, 2))
        # batch 3 cannot split over dp=2 -> replicated; 4 can
        assert shardlib.fit_spec(P("dp", None), (3, 8), mesh) == P(None, None)
        assert shardlib.fit_spec(P("dp", None), (4, 8), mesh) == P("dp", None)


class TestServeKVSpecs:
    def test_kv_heads_shard_when_divisible(self):
        mesh = fake_mesh((1, 2))
        # page pool (L, N+1, Hkv, bs, D): ONLY the head axis shards
        spec = shardlib.serve_kv_spec((2, 9, 2, 16, 32), mesh)
        assert spec == P(None, None, "mp", None, None)

    def test_kv_replicates_when_heads_indivisible(self):
        """Hkv=2 at mp=4: the invariant is replicate, not reshard —
        page ids must index the same N axis on every shard."""
        mesh = fake_mesh((1, 4))
        spec = shardlib.serve_kv_spec((2, 9, 2, 16, 32), mesh)
        assert all(a is None for a in spec)

    def test_cache_specs_keep_tables_host_side(self):
        """k/v shard; length and block tables replicate — block tables
        are host-side ints and must never become mesh-aware."""
        mesh = fake_mesh((1, 2))
        sds = jax.ShapeDtypeStruct
        tree = {"k": sds((2, 4, 2, 16, 32), np.float32),
                "v": sds((2, 4, 2, 16, 32), np.float32),
                "length": sds((4,), np.int32),
                "bt": sds((4, 6), np.int32)}
        specs = shardlib.serve_cache_specs(tree, mesh)
        assert specs["k"] == P(None, None, "mp", None, None)
        assert specs["v"] == P(None, None, "mp", None, None)
        assert specs["length"] == P()
        assert specs["bt"] == P()

    def test_serve_mesh_validates(self):
        devs = [object() for _ in range(4)]
        m = shardlib.serve_mesh(2, 2, devices=devs)
        assert m.axis_names == ("dp", "mp")
        assert m.devices.shape == (2, 2)
        with pytest.raises(ValueError, match="devices"):
            shardlib.serve_mesh(2, 4, devices=devs)
        with pytest.raises(ValueError, match=">= 1"):
            shardlib.serve_mesh(0, 1, devices=devs)


class TestShardBucket:
    def test_shard_segment_renders_in_label(self):
        bucket = occupancy_bucket(2, 4) + shard_bucket(1, 2)
        assert "mesh:dp1mp2" in bucket_label(bucket)

    def test_distinct_meshes_are_distinct_keys(self):
        base = occupancy_bucket(2, 4)
        keys = {base + shard_bucket(1, 1), base + shard_bucket(1, 2),
                base + shard_bucket(2, 1), base + shard_bucket(2, 2)}
        assert len(keys) == 4

    def test_trivial_mesh_appends_nothing_to_engine_keys(self):
        """(1,1) must be a bitwise no-op down to the dispatch keys."""
        params = model.init_params(CFG, jax.random.PRNGKey(0))
        eng = ContinuousBatchingEngine(CFG, params, slots=2, max_len=32)
        assert eng._shard_tail == ()


# -- device-gated engine parity ------------------------------------------------

NDEV = jax.device_count()
needs_devices = pytest.mark.skipif(
    NDEV < 2,
    reason="needs multiple host devices: run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def setup():
    params = model.init_params(CFG, jax.random.PRNGKey(0))
    return CFG, params


def _workload(vocab):
    rng = np.random.default_rng(21)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab,
                                        int(rng.integers(5, 13))).astype(np.int32),
                    max_new_tokens=6)
            for i in range(6)]


def _run(eng, vocab):
    for r in _workload(vocab):
        eng.submit(r)
    done = eng.run()
    eng.check_kv()          # zero leaked pages at drain
    return {r.rid: r.out for r in done}


_BASELINE = {}


def _baseline(cfg, params, kv_layout):
    if kv_layout not in _BASELINE:
        eng = ContinuousBatchingEngine(cfg, params, slots=2, max_len=48,
                                       kv_layout=kv_layout, block_size=8)
        _BASELINE[kv_layout] = _run(eng, cfg.vocab_size)
    return _BASELINE[kv_layout]


@needs_devices
class TestMeshParity:
    """Token-exact parity vs the unsharded engine, every layout x mesh."""

    @pytest.mark.parametrize("kv_layout", ("contiguous", "paged", "auto"))
    @pytest.mark.parametrize("mesh_shape", ((1, 1), (1, 2), (2, 1)))
    def test_token_parity(self, setup, mesh_shape, kv_layout):
        cfg, params = setup
        want = _baseline(cfg, params, kv_layout)
        eng = make_serve_engine(cfg, params, mesh_shape=mesh_shape,
                                slots=2, max_len=48, kv_layout=kv_layout,
                                block_size=8)
        got = _run(eng, cfg.vocab_size)
        assert got == want, f"mesh {mesh_shape} diverged on {kv_layout}"

    def test_dp_group_shares_one_queue(self, setup):
        """dp=2: both replicas serve, the shared queue drains, and the
        merged stats see every request exactly once."""
        cfg, params = setup
        group = make_serve_engine(cfg, params, mesh_shape=(2, 1),
                                  slots=1, max_len=48)
        assert isinstance(group, EngineReplicaGroup)
        reqs = _workload(cfg.vocab_size)
        for r in reqs:
            group.submit(r)
        done = group.run()
        assert sorted(r.rid for r in done) == [r.rid for r in reqs]
        assert group.queue == []
        # with 1 slot per replica and 6 requests, a single replica
        # cannot have served them all
        per_replica = [len(e.completed) for e in group.engines]
        assert all(n > 0 for n in per_replica)
        assert len(group.stats.queue_wait_s) == len(reqs)
        group.check_kv()

    @pytest.mark.skipif(NDEV < 4, reason="needs 4 devices for dp2 x mp2")
    def test_dp_mp_combined_parity(self, setup):
        cfg, params = setup
        want = _baseline(cfg, params, "paged")
        group = make_serve_engine(cfg, params, mesh_shape=(2, 2),
                                  slots=2, max_len=48, kv_layout="paged",
                                  block_size=8)
        got = _run(group, cfg.vocab_size)
        assert got == want

    @pytest.mark.parametrize("kv_layout", ("paged", "auto"))
    @pytest.mark.parametrize("mesh_shape", ((1, 1), (1, 2), (2, 1)))
    def test_pallas_backend_token_parity(self, setup, mesh_shape, kv_layout):
        """Pinned Pallas on both kernel axes (chunked prefill + fused
        horizon) == the unsharded gather baseline, token for token.
        Hkv=2 divides mp=2, so the (1,2) case runs the kernel on true
        Hkv/mp head slices per shard (docs/kernel_variants.md)."""
        cfg, params = setup
        want = _baseline(cfg, params, kv_layout)
        eng = make_serve_engine(cfg, params, mesh_shape=mesh_shape,
                                slots=2, max_len=48, kv_layout=kv_layout,
                                block_size=8, prefill_chunk=8,
                                decode_horizon=4, decode_impl="pallas",
                                prefill_kernel="pallas")
        got = _run(eng, cfg.vocab_size)
        assert got == want, f"pallas mesh {mesh_shape} diverged on {kv_layout}"

    @pytest.mark.skipif(NDEV < 4, reason="needs 4 devices for mp=4")
    def test_pallas_indivisible_heads_fall_back(self, setup):
        """Hkv=2 at mp=4 forces KV replication (serve_kv_spec), so
        kernel_shard_ok gates Pallas off; a pinned 'pallas' must resolve
        down the ladder to the gather path and keep parity, not crash."""
        cfg, params = setup
        want = _baseline(cfg, params, "paged")
        eng = make_serve_engine(cfg, params, mesh_shape=(1, 4), slots=2,
                                max_len=48, kv_layout="paged", block_size=8,
                                decode_impl="pallas", prefill_kernel="pallas")
        assert not eng._pallas_ok
        got = _run(eng, cfg.vocab_size)
        assert got == want

    def test_pallas_pinned_on_contiguous_resolves_to_grouped(self, setup):
        """No pages to index: a contiguous engine pins 'pallas' through
        the first fallback rung (delegates to the grouped path)."""
        cfg, params = setup
        want = _baseline(cfg, params, "contiguous")
        eng = make_serve_engine(cfg, params, mesh_shape=(1, 2), slots=2,
                                max_len=48, kv_layout="contiguous",
                                decode_impl="pallas")
        assert not eng._pallas_ok
        got = _run(eng, cfg.vocab_size)
        assert got == want

    def test_shard_tail_reaches_dispatch_keys(self, setup):
        """A sharded engine's decode selections must be keyed per mesh
        configuration (the tentpole's VPE contract)."""
        from repro.core import VPE
        cfg, params = setup
        vpe = VPE(controller_kwargs=dict(min_samples=2, trial_samples=2))
        eng = make_serve_engine(cfg, params, mesh_shape=(1, 2), slots=2,
                                max_len=48, vpe=vpe)
        for r in _workload(cfg.vocab_size):
            eng.submit(r)
        eng.run()
        keys = [b for (op, b) in vpe.controller._decisions
                if op == "serve_decode_impl"]
        assert keys and all("shard" in b and (1, 2) == b[-2:] for b in keys)


_REPLICA_PLACEMENT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.configs import ARCHS
from repro.launch.serve import init_serve_params
from repro.runtime.serve_loop import make_serve_engine

cfg = ARCHS["qwen3-8b"].reduced()
devs = jax.devices()
params = init_serve_params(cfg, 0, mp=2)

def per_device(tree):
    out = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out

total = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
made = per_device(params)
assert set(made) == {devs[0].id, devs[1].id}, made
assert max(made.values()) < total, (made, total)
group = make_serve_engine(cfg, params, mesh_shape=(2, 2), slots=2,
                          max_len=32, kv_layout="paged")
r0, r1 = group.engines
assert all(a is b for a, b in zip(jax.tree.leaves(r0.params),
                                  jax.tree.leaves(params)))
held = per_device(r1.params)
assert set(held) == {devs[2].id, devs[3].id}, held
assert held[devs[2].id] == made[devs[0].id], (held, made)
print("PLACEMENT-OK")
"""


def test_replica_group_params_never_gather_on_one_device():
    """``--mesh 2,2``: parameters are made sharded on the first replica
    row; replica 0 keeps them and replica 1 copies only its own shards,
    so no device holds the whole model.  Four forced host devices in a
    subprocess keep this session's single CPU device."""
    import os
    import subprocess
    import sys
    root = os.path.join(os.path.dirname(__file__), "..")
    out = subprocess.run(
        [sys.executable, "-c", _REPLICA_PLACEMENT], capture_output=True,
        text=True, timeout=300, cwd=root,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    assert "PLACEMENT-OK" in out.stdout, out.stderr[-3000:]
