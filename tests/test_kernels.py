"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

All kernels run in interpret mode on CPU (the container has no TPU);
tolerances reflect f32 accumulation-order differences only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def arr(shape, dtype=np.float32):
    return jnp.asarray(RNG.standard_normal(shape).astype(dtype))


TOL = {np.float32: dict(rtol=2e-4, atol=2e-4)}


class TestMatmul:
    @pytest.mark.parametrize("m,k,n", [
        (128, 256, 128),   # exact blocks
        (256, 512, 256),   # multi-block
        (100, 200, 60),    # padding path
        (8, 8, 8),         # tiny
        (1, 512, 128),     # degenerate row -> oracle fallback
        (384, 128, 384),
    ])
    def test_against_oracle(self, m, k, n):
        a, b = arr((m, k)), arr((k, n))
        got = ops.matmul(a, b)
        want = ref.matmul_ref(a, b)
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)

    def test_bf16_inputs(self):
        a = arr((128, 256)).astype(jnp.bfloat16)
        b = arr((256, 128)).astype(jnp.bfloat16)
        got = ops.matmul(a, b).astype(np.float32)
        want = ref.matmul_ref(a, b).astype(np.float32)
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("bm,bk,bn", [(32, 64, 32), (64, 32, 128)])
    def test_block_shape_sweep(self, bm, bk, bn):
        a, b = arr((128, 128)), arr((128, 128))
        got = ops.matmul(a, b, bm=bm, bk=bk, bn=bn)
        np.testing.assert_allclose(got, ref.matmul_ref(a, b), rtol=5e-4, atol=5e-4)


class TestConv2d:
    @pytest.mark.parametrize("h,w,k", [
        (64, 64, 3), (64, 64, 5), (37, 53, 5), (128, 96, 11), (16, 16, 3),
    ])
    def test_against_oracle(self, h, w, k):
        x, ker = arr((h, w)), arr((k, k))
        got = ops.conv2d(x, ker)
        want = ref.conv2d_ref(x, ker)
        assert got.shape == (h - k + 1, w - k + 1)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("bh", [4, 8, 16])
    def test_row_block_sweep(self, bh):
        x, ker = arr((66, 64)), arr((3, 3))
        got = ops.conv2d(x, ker, bh=bh)
        np.testing.assert_allclose(got, ref.conv2d_ref(x, ker), rtol=2e-4, atol=2e-4)


class TestFlashAttention:
    @pytest.mark.parametrize("case", [
        dict(B=2, Hq=4, Hkv=4, S=64, T=64, D=32, causal=True, window=None),
        dict(B=1, Hq=8, Hkv=2, S=64, T=64, D=32, causal=True, window=None),   # GQA
        dict(B=1, Hq=4, Hkv=2, S=64, T=64, D=32, causal=True, window=16),    # SWA
        dict(B=1, Hq=4, Hkv=2, S=96, T=96, D=32, causal=False, window=None), # encoder
        dict(B=2, Hq=4, Hkv=2, S=1, T=80, D=32, causal=True, window=None),   # decode
        dict(B=1, Hq=4, Hkv=2, S=40, T=72, D=32, causal=True, window=None),  # ragged
        dict(B=1, Hq=4, Hkv=1, S=64, T=64, D=64, causal=True, window=8),     # narrow window
    ])
    def test_against_oracle(self, case):
        B, Hq, Hkv, S, T, D = (case[k] for k in ("B", "Hq", "Hkv", "S", "T", "D"))
        q, k, v = arr((B, Hq, S, D)), arr((B, Hkv, T, D)), arr((B, Hkv, T, D))
        got = ops.flash_attention(q, k, v, causal=case["causal"],
                                  window=case["window"], bq=32, bk=32)
        want = ref.attention_ref(q, k, v, causal=case["causal"], window=case["window"])
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)

    def test_block_size_independence(self):
        q, k, v = arr((1, 4, 128, 32)), arr((1, 2, 128, 32)), arr((1, 2, 128, 32))
        outs = [ops.flash_attention(q, k, v, bq=bq, bk=bk)
                for bq, bk in [(32, 32), (64, 32), (32, 64), (128, 128)]]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], rtol=3e-4, atol=3e-4)

    def test_softmax_rows_normalized(self):
        """Output of attention over constant V must be that constant."""
        q, k = arr((1, 2, 64, 16)), arr((1, 2, 64, 16))
        v = jnp.ones((1, 2, 64, 16), jnp.float32) * 3.0
        got = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
        np.testing.assert_allclose(got, 3.0 * np.ones_like(got), rtol=1e-5, atol=1e-5)

    def test_grad_matches_reference(self):
        from repro.models.layers import attention_chunked, attention_flash
        q, k, v = arr((1, 2, 64, 16)), arr((1, 2, 64, 16)), arr((1, 2, 64, 16))

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss(attention_flash), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(attention_chunked), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


class TestPagedAttention:
    """Block-indirect decode attention: the Pallas kernel must equal the
    gather-based oracle (which the paged-parity suite separately proves
    equal to contiguous decode attention on identical page contents)."""

    def _pool(self, rng, N, Hkv, bs, D):
        kp = rng.standard_normal((N, Hkv, bs, D)).astype(np.float32)
        vp = rng.standard_normal((N, Hkv, bs, D)).astype(np.float32)
        return jnp.asarray(kp), jnp.asarray(vp)

    # poisoned cases: lengths at the page edges (0, bs-1, bs, nb*bs-1),
    # every page outside a row's live range NaN, in both read modes
    EDGES = dict(B=6, Hq=4, Hkv=2, bs=8, nb=4, D=32,
                 lengths=(0, 7, 8, 31, 13, 20))

    @pytest.mark.parametrize("case", [
        dict(B=3, Hq=4, Hkv=2, bs=8, nb=4, D=32, window=None),   # GQA
        dict(B=2, Hq=4, Hkv=2, bs=8, nb=6, D=32, window=9),      # SWA
        dict(B=2, Hq=4, Hkv=4, bs=16, nb=3, D=16, window=None),  # MHA
        dict(B=1, Hq=8, Hkv=1, bs=4, nb=8, D=64, window=None),   # MQA
        dict(EDGES, window=None, poison=True),
        dict(EDGES, window=None, poison=True, read_dtype=jnp.bfloat16),
        dict(EDGES, window=9, poison=True),
        dict(EDGES, window=9, poison=True, read_dtype=jnp.bfloat16),
        dict(B=4, Hq=8, Hkv=1, bs=4, nb=8, D=64, window=None,    # MQA
             lengths=(0, 3, 4, 31), poison=True),
    ])
    def test_against_oracle(self, case):
        """The kernel equals the gather oracle.  Poisoned cases fill
        every page no row reads with NaN (past a row's length, before
        its sliding window's first page, the trash page repeated in the
        tables' tails): the kernel must not touch them, so its output is
        finite and equals the oracle over the clean pool.  With
        ``read_dtype`` the kernel also rounds K, V and the probabilities
        to bfloat16, which the tolerance admits."""
        from repro.kernels.paged_attention import paged_attention_pallas
        rng = np.random.default_rng(0)
        B, Hq, Hkv, bs, nb, D = (case[k]
                                 for k in ("B", "Hq", "Hkv", "bs", "nb", "D"))
        window, read_dtype = case["window"], case.get("read_dtype")
        N = nb * B
        kp, vp = self._pool(rng, N, Hkv, bs, D)
        q = jnp.asarray(rng.standard_normal((B, Hq, 1, D)).astype(np.float32))
        # rows share pages (the aliased-prefix shape) and repeat padding
        bt = rng.integers(0, N, (B, nb)).astype(np.int32)
        bt[1:, 0] = bt[0, 0]
        lengths = rng.integers(0, nb * bs, (B,)).astype(np.int32)
        dead = None
        if case.get("poison"):
            lengths = np.asarray(case["lengths"], np.int32)
            bt, live = self._live_tables(rng, lengths, nb, bs, window)
            N = bt.max() + 1
            kp, vp = self._pool(rng, N, Hkv, bs, D)
            dead = jnp.asarray(~np.isin(np.arange(N), live))[:, None, None,
                                                              None]
        if read_dtype is not None:   # values the bf16 read keeps exactly
            kp, vp = (x.astype(read_dtype).astype(jnp.float32)
                      for x in (kp, vp))
        k_in, v_in = ((kp, vp) if dead is None else
                      (jnp.where(dead, jnp.nan, x) for x in (kp, vp)))
        got = paged_attention_pallas(q, k_in, v_in, jnp.asarray(bt),
                                     jnp.asarray(lengths), window=window,
                                     read_dtype=read_dtype)
        want = ref.paged_attention_ref(q, kp, vp, jnp.asarray(bt),
                                       jnp.asarray(lengths), window=window)
        assert np.isfinite(np.asarray(got)).all()
        tol = 3e-5 if read_dtype is None else 1e-2
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol)

    @staticmethod
    def _live_tables(rng, lengths, nb, bs, window):
        """Block tables whose live entries hold distinct pages, except
        the first live page, which every row shares; the entries before
        a window's first page hold pages of their own and every entry
        past a row's last page holds the trash page (the largest id).
        Returns (tables, the ids some row reads)."""
        B = len(lengths)
        bt = np.arange(B * nb, dtype=np.int32).reshape(B, nb)
        rng.shuffle(bt.reshape(-1))
        trash = B * nb
        live = []
        for b, n in enumerate(lengths):
            first = 0 if window is None else max(0, (n - window + 1) // bs)
            last = n // bs
            bt[b, first] = bt[0, 0]
            bt[b, last + 1:] = trash
            live.extend(bt[b, first:last + 1])
        return bt, np.unique(live)

    @pytest.mark.parametrize("case", [
        dict(B=2, Hq=4, Hkv=2, bs=8, nb=6, C=16, D=32, window=None),  # GQA
        dict(B=2, Hq=4, Hkv=2, bs=8, nb=6, C=8, D=32, window=11),     # SWA
        dict(B=1, Hq=8, Hkv=1, bs=4, nb=8, C=12, D=64, window=None),  # MQA
        dict(B=3, Hq=4, Hkv=4, bs=16, nb=4, C=1, D=16, window=None),  # C=1
    ])
    def test_prefill_kernel_against_oracle(self, case):
        """Multi-query (chunked-prefill) kernel == gather-based oracle:
        every query row i of sequence b masks at its own causal frontier
        base[b] + i, pages read through the same scalar-prefetch
        indirection as decode."""
        from repro.kernels.paged_attention import paged_prefill_attention_pallas
        rng = np.random.default_rng(2)
        B, Hq, Hkv, bs, nb, C, D = (case[k] for k in
                                    ("B", "Hq", "Hkv", "bs", "nb", "C", "D"))
        N = nb * B
        kp, vp = self._pool(rng, N, Hkv, bs, D)
        q = jnp.asarray(rng.standard_normal((B, Hq, C, D)).astype(np.float32))
        bt = rng.integers(0, N, (B, nb)).astype(np.int32)
        # bases leave room for the whole chunk inside the table
        base = rng.integers(0, nb * bs - C + 1, (B,)).astype(np.int32)
        got = paged_prefill_attention_pallas(
            q, kp, vp, jnp.asarray(bt), jnp.asarray(base),
            window=case["window"])
        want = ref.paged_prefill_attention_ref(
            q, kp, vp, jnp.asarray(bt), jnp.asarray(base),
            window=case["window"])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)

    def test_prefill_kernel_chunk_len_padding(self):
        """Padded queries past chunk_len must not disturb real rows, and
        columns past base + chunk_len (unwritten pages) are masked."""
        from repro.kernels.paged_attention import paged_prefill_attention_pallas
        rng = np.random.default_rng(3)
        B, Hq, Hkv, bs, nb, C, D, clen = 1, 4, 2, 8, 4, 16, 32, 11
        kp, vp = self._pool(rng, nb, Hkv, bs, D)
        q = jnp.asarray(rng.standard_normal((B, Hq, C, D)).astype(np.float32))
        bt = jnp.asarray(np.arange(nb, dtype=np.int32)[None])
        base = jnp.asarray(np.array([8], np.int32))
        got = paged_prefill_attention_pallas(q, kp, vp, bt, base,
                                             chunk_len=clen)
        want = ref.paged_prefill_attention_ref(q, kp, vp, bt, base,
                                               chunk_len=clen)
        np.testing.assert_allclose(np.asarray(got)[:, :, :clen],
                                   np.asarray(want)[:, :, :clen],
                                   rtol=3e-5, atol=3e-5)

    def test_prefill_ref_matches_plain_attention(self):
        """On an identity table covering exactly base + C positions the
        multi-query oracle equals stock causal attention — anchoring the
        chunked path to the monolithic prefill's math."""
        rng = np.random.default_rng(4)
        Hq, Hkv, bs, nb, C, D = 4, 2, 8, 4, 16, 32
        base = nb * bs - C                                # T == base + C
        kp, vp = self._pool(rng, nb, Hkv, bs, D)
        q = jnp.asarray(rng.standard_normal((1, Hq, C, D)).astype(np.float32))
        bt = jnp.asarray(np.arange(nb, dtype=np.int32)[None])

        def lin(pool):
            return pool.transpose(1, 0, 2, 3).reshape(1, Hkv, nb * bs, D)

        for window in (None, 9):
            got = ref.paged_prefill_attention_ref(
                q, kp, vp, bt, jnp.asarray(np.array([base], np.int32)),
                window=window)
            want = ref.attention_ref(q, lin(kp), lin(vp), causal=True,
                                     window=window)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)

    def test_matches_contiguous_decode_attention(self):
        """Linearizing pages through the table reproduces the engine's
        contiguous decode attention exactly — the layout-parity anchor."""
        from repro.models import kvcache
        rng = np.random.default_rng(1)
        B, Hkv, bs, nb, D = 2, 2, 8, 4, 32
        kp, vp = self._pool(rng, B * nb, Hkv, bs, D)
        q = jnp.asarray(rng.standard_normal((B, 4, 1, D)).astype(np.float32))
        bt = jnp.asarray(np.arange(B * nb, dtype=np.int32).reshape(B, nb))
        lengths = jnp.asarray(np.array([13, 29], np.int32))
        kg, vg = kvcache.paged_gather_layer(kp, vp, bt)
        want = kvcache.decode_attention(q, kg, vg, lengths)
        got = ref.paged_attention_ref(q, kp, vp, bt, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_decode_kernel_read_dtype_quantizes_kv(self):
        """The engine's paged decode gathers pages at bf16
        (SLOT_CACHE_DTYPE) and ``decode_attention`` additionally casts
        the softmax probabilities to the cache dtype before the value
        einsum.  With read_dtype set the kernel reproduces BOTH
        quantizations (scores kept in VMEM until the final stats are
        known, then a V pass that accumulates bf16(p) @ bf16(v)), so it
        must match the real serve gather path — not just an f32 oracle
        over pre-quantized pools — to well under the ~4e-3 gap that flipped
        greedy tokens when p stayed in f32.  Exact end-to-end greedy
        parity rides on this and is proven by TestEngineKernelVariants
        below."""
        from repro.kernels.paged_attention import paged_attention_pallas
        from repro.models import kvcache
        rng = np.random.default_rng(5)
        B, Hq, Hkv, bs, nb, D = 2, 4, 2, 8, 4, 32
        kp, vp = self._pool(rng, B * nb, Hkv, bs, D)
        q = jnp.asarray(rng.standard_normal((B, Hq, 1, D)).astype(np.float32))
        bt = jnp.asarray(np.arange(B * nb, dtype=np.int32).reshape(B, nb))
        lengths = jnp.asarray(np.array([13, 29], np.int32))
        quant = kvcache.SLOT_CACHE_DTYPE
        kg, vg = kvcache.paged_gather_layer(kp, vp, bt, out_dtype=quant)
        want = kvcache.decode_attention(q, kg, vg, lengths)
        got = paged_attention_pallas(q, kp, vp, bt, lengths,
                                     read_dtype=quant)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-4, atol=5e-4)


class TestKernelShardContract:
    """docs/sharding.md head-slice contract: under mp each shard's
    kernel sees its LOCAL Hkv/mp head slice with the full unsharded
    page axis.  Running the kernel per slice and concatenating the
    matching q-head groups must equal the full-head kernel — the
    property that makes shard_map-free jit sharding of the pallas
    variants legal whenever Hkv % mp == 0."""

    Hq, Hkv, bs, nb, D = 8, 4, 8, 4, 32
    group = Hq // Hkv   # q heads h*group:(h+1)*group attend kv head h

    def _data(self, B, C=1, seed=7):
        rng = np.random.default_rng(seed)
        N = B * self.nb
        kp = jnp.asarray(rng.standard_normal(
            (N, self.Hkv, self.bs, self.D)).astype(np.float32))
        vp = jnp.asarray(rng.standard_normal(
            (N, self.Hkv, self.bs, self.D)).astype(np.float32))
        q = jnp.asarray(rng.standard_normal(
            (B, self.Hq, C, self.D)).astype(np.float32))
        bt = jnp.asarray(rng.integers(0, N, (B, self.nb)).astype(np.int32))
        return kp, vp, q, bt

    def _head_slices(self, mp):
        """(q_slice, kv_slice) per shard for an Hkv % mp == 0 split."""
        kv_per = self.Hkv // mp
        for s in range(mp):
            kv = slice(s * kv_per, (s + 1) * kv_per)
            qs = slice(kv.start * self.group, kv.stop * self.group)
            yield qs, kv

    @pytest.mark.parametrize("mp", [1, 2])
    def test_decode_kernel_shards_by_head_slice(self, mp):
        from repro.kernels.paged_attention import paged_attention_pallas
        kp, vp, q, bt = self._data(B=2)
        lengths = jnp.asarray(np.array([11, 27], np.int32))
        full = paged_attention_pallas(q, kp, vp, bt, lengths)
        parts = [paged_attention_pallas(q[:, qs], kp[:, kv], vp[:, kv],
                                        bt, lengths)
                 for qs, kv in self._head_slices(mp)]
        got = jnp.concatenate(parts, axis=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("mp", [1, 2])
    def test_prefill_kernel_shards_by_head_slice(self, mp):
        from repro.kernels.paged_attention import paged_prefill_attention_pallas
        kp, vp, q, bt = self._data(B=2, C=12, seed=8)
        base = jnp.asarray(np.array([4, 16], np.int32))
        full = paged_prefill_attention_pallas(q, kp, vp, bt, base,
                                              chunk_len=12)
        parts = [paged_prefill_attention_pallas(
                     q[:, qs], kp[:, kv], vp[:, kv], bt, base, chunk_len=12)
                 for qs, kv in self._head_slices(mp)]
        got = jnp.concatenate(parts, axis=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                                   rtol=1e-5, atol=1e-5)

    @staticmethod
    def _fake_mesh(shape, axes=("dp", "mp")):
        from jax.sharding import Mesh

        class Dev:
            def __init__(self, i):
                self.id = i
        n = int(np.prod(shape))
        return Mesh(np.array([Dev(i) for i in range(n)],
                             dtype=object).reshape(shape), axes)

    def test_kernel_shard_ok(self):
        from repro.distributed.sharding import kernel_shard_ok
        fake_mesh = self._fake_mesh
        assert kernel_shard_ok(2, None)                 # no mesh
        assert kernel_shard_ok(2, fake_mesh((1, 1)))    # trivial mp
        assert kernel_shard_ok(2, fake_mesh((1, 2)))    # 2 % 2 == 0
        assert kernel_shard_ok(4, fake_mesh((2, 2)))    # dp ignored
        assert not kernel_shard_ok(2, fake_mesh((1, 3)))   # replicated KV
        assert not kernel_shard_ok(3, fake_mesh((1, 2)))


class TestEngineKernelVariants:
    """Serve-engine integration on one CPU device (tier-1): the pallas
    backends are controller-selectable, fall back down the capability
    ladder, and keep token-exact greedy parity with the gather paths."""

    @pytest.fixture(scope="class")
    def setup(self):
        from repro.configs import ARCHS
        from repro.models import model
        cfg = ARCHS["qwen3-8b"].reduced()
        params = model.init_params(cfg, jax.random.PRNGKey(0))
        return cfg, params

    def _run(self, eng, vocab):
        from repro.runtime.serve_loop import Request
        rng = np.random.default_rng(11)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, vocab, int(rng.integers(5, 14))
                                            ).astype(np.int32),
                        max_new_tokens=6)
                for i in range(5)]
        for r in reqs:
            eng.submit(r)
        done = eng.run()
        eng.check_kv()          # zero leaked pages at drain
        return {r.rid: r.out for r in done}

    @pytest.mark.parametrize("kv_layout", ["paged", "auto"])
    def test_pallas_token_parity(self, setup, kv_layout):
        """Pinned pallas on both axes == pinned gather, across chunked
        prefill and a fused decode horizon."""
        from repro.runtime.serve_loop import ContinuousBatchingEngine
        cfg, params = setup
        outs = {}
        for decode_impl, prefill_kernel in (("grouped", "gather"),
                                            ("pallas", "pallas")):
            eng = ContinuousBatchingEngine(
                cfg, params, slots=2, max_len=48, kv_layout=kv_layout,
                block_size=8, prefill_chunk=8, decode_horizon=4,
                decode_impl=decode_impl, prefill_kernel=prefill_kernel)
            outs[decode_impl] = self._run(eng, cfg.vocab_size)
        assert outs["pallas"] == outs["grouped"]

    def test_capability_gate_and_resolution(self, setup):
        """paged engine on CPU passes the interpret-mode probe; gating
        it off resolves pallas down the ladder; a contiguous engine is
        never pallas-capable (no pages to index)."""
        from repro.runtime.serve_loop import ContinuousBatchingEngine
        cfg, params = setup
        eng = ContinuousBatchingEngine(cfg, params, slots=2, max_len=48,
                                       kv_layout="paged", block_size=8)
        assert eng._pallas_ok
        assert eng._resolve_impl("pallas") == "pallas"
        assert eng._resolve_kernel("pallas") == "pallas"
        eng._pallas_ok = False
        assert eng._resolve_impl("pallas") == "grouped"
        assert eng._resolve_kernel("pallas") == "gather"
        cont = ContinuousBatchingEngine(cfg, params, slots=2, max_len=48)
        assert not cont._pallas_ok

    def test_auto_axes_register_pallas_variants(self, setup):
        """kv_layout=paged + vpe: both measured axes expose the pallas
        variant to the controller; pinning an axis registers it as a
        system op (recorded, never trialed)."""
        from repro.core import VPE
        from repro.runtime.serve_loop import ContinuousBatchingEngine
        cfg, params = setup
        vpe = VPE()
        ContinuousBatchingEngine(cfg, params, slots=2, max_len=48,
                                 kv_layout="paged", block_size=8, vpe=vpe)
        assert set(vpe.registry.op("serve_decode_impl").variants) >= {
            "grouped", "flat", "pallas"}
        assert set(vpe.registry.op("prefill_kernel").variants) == {
            "gather", "pallas"}
        vpe2 = VPE()
        ContinuousBatchingEngine(cfg, params, slots=2, max_len=48,
                                 kv_layout="paged", block_size=8, vpe=vpe2,
                                 decode_impl="pallas", prefill_kernel="pallas")
        assert vpe2.registry.op("serve_decode_impl").system
        assert not vpe2.registry.has_op("prefill_kernel")
