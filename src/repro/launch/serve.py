"""Serving launcher: batched generation, wave or continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        --requests 8 --new-tokens 16 [--continuous]
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.core import VPE
from repro.distributed import sharding as sharding_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.runtime.serve_faults import FaultPlan
from repro.runtime.serve_loop import (
    Request, ServeLoop, WaveScheduler, make_serve_engine)


_EPILOG = """\
kernel backends (--decode-impl / --prefill-kernel):
  'pallas' runs the block-indirect Pallas kernels over the paged KV
  layout (scalar-prefetch block tables; interpret=True off-TPU);
  'grouped'/'flat'/'gather' are the stock jnp paths; 'auto' (default)
  lets the VPE controller measure both backends per bucket x mesh and
  route to the winner.  Fallback ladder (docs/kernel_variants.md): a
  pinned or selected 'pallas' degrades to the gather path when the
  layout has no pages or the mesh's Hkv % mp != 0 forces KV
  replication.  A kernel that fails to compile or run is an error.

speculative decoding (--spec-draft):
  an n-gram table drafted from emitted tokens proposes up to S-1
  candidates per slot; ONE verify pass scores all S positions and
  commits the longest matching prefix + one correction token — greedy
  output is token-exact vs the plain path by construction.  'off'
  (default) disables it, an INT pins the verify span, 'auto' registers
  a VPE axis keyed by queue-depth x occupancy x measured accept-rate
  level that learns per bucket when speculation beats plain fused
  horizons.  Fallback ladder (same no-crash pin-resolution discipline
  as --decode-impl): --kv-layout contiguous (no block table to write
  candidates through) and --decode-horizon 1 (multi-token device calls
  opted out) resolve any requested spec-draft to 'off'; a span larger
  than a slot's remaining budget falls back to the plain path for that
  step — it never crashes.

fault tolerance (--fault-seed / --watchdog / --deadline):
  --fault-seed arms a reproducible fault storm (--fault-storm faults
  drawn over the engine's fenced spans: decode / fused / spec verify /
  prefill chunk / page alloc / replica dispatch) that raises device
  errors, poisons logits to NaN, or stalls the fence at planned
  coordinates.  Recovery never surfaces to the caller: the engine
  quarantines the faulting variant one ladder rung at a time (pallas
  -> gather, spec -> off, horizon -> 1, re-promoted after a clean
  probation window), replays poisoned slots from their exact committed
  prefix, and fails only requests whose own fault budget is spent —
  with a reason code and complete latency record.  --watchdog arms the
  straggler fence watchdog (stalls demote instead of hanging);
  --deadline and --max-queue-depth bound latency and queue depth by
  shedding, also with reason codes.  docs/fault_tolerance.md has the
  full failure model.
"""


def init_serve_params(cfg, seed: int, mp: int):
    """Parameters made in place on the first replica row's ``mp``
    devices (sharded there when ``mp > 1``, else on the default
    device).  Replica 0 then keeps them where they are and every other
    replica copies its own shards from there, so no device ever holds
    more than one replica's shard of the model."""
    mesh = sharding_lib.serve_mesh(1, mp) if mp > 1 else None
    return model_lib.init_params(cfg, jax.random.PRNGKey(seed), mesh=mesh)


def main() -> None:
    ap = argparse.ArgumentParser(
        epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="token-level continuous batching (VPE-tuned decode)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree shared-prefix KV cache (continuous only)")
    ap.add_argument("--prefix-blocks", type=int, default=64,
                    help="KV page pool size for --prefix-cache")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV page for --prefix-cache/--kv-layout")
    ap.add_argument("--kv-layout", choices=["contiguous", "paged", "auto"],
                    default="contiguous",
                    help="slot KV layout: contiguous per-slot regions, "
                         "paged block tables over the unified page pool "
                         "(O(1) prefix admission), or auto — a VPE axis "
                         "measured per matched-length x occupancy bucket")
    ap.add_argument("--prefill-chunk", default="whole",
                    help="paged prefill chunk size in tokens, 'whole' "
                         "(one chunk per prompt), or 'auto' — a VPE axis "
                         "measured per prompt-length x occupancy bucket; "
                         "chunks interleave with decode steps so long "
                         "prompts cannot stall resident requests")
    ap.add_argument("--chunks-per-step", type=int, default=None,
                    help="prefill chunks run per engine step (the decode "
                         "stall budget); default adapts to occupancy — "
                         "1 with resident decoders, one per prefilling "
                         "slot when nothing decodes")
    ap.add_argument("--decode-horizon", default="1",
                    help="decode steps fused into one on-device loop per "
                         "engine step (int), or 'auto' — a VPE axis keyed "
                         "by queue-depth x occupancy, fed from per-token "
                         "wall time: long horizons amortize host dispatch "
                         "when the queue is empty, 1 keeps admission "
                         "latency bounded under load")
    ap.add_argument("--spec-draft", default="off",
                    help="speculative verify span: 'off', an int S "
                         "(one pass scores S positions: last committed "
                         "token + S-1 n-gram drafts), or 'auto' — a VPE "
                         "axis keyed by queue-depth x occupancy x accept-"
                         "rate bucket, fed per committed token (see "
                         "epilog for the fallback ladder)")
    ap.add_argument("--decode-impl",
                    choices=["grouped", "flat", "pallas", "auto"],
                    default="auto",
                    help="decode attention backend: stock jnp paths "
                         "('grouped'/'flat'), the block-indirect Pallas "
                         "kernel over paged KV ('pallas'), or 'auto' — "
                         "the serve_decode_impl VPE axis measured per "
                         "occupancy bucket x mesh (see epilog)")
    ap.add_argument("--prefill-kernel",
                    choices=["gather", "pallas", "auto"],
                    default="auto",
                    help="paged chunked-prefill backend: 'gather' "
                         "linearizes pages in-jit, 'pallas' scores them "
                         "in place, 'auto' measures both per prefill-"
                         "chunk bucket x mesh (see epilog)")
    ap.add_argument("--priority", choices=["batch", "interactive", "mix"],
                    default="batch",
                    help="request priority class; 'mix' alternates "
                         "interactive/batch to exercise the class-aware "
                         "scheduler (preemption + per-class starvation "
                         "bounds)")
    ap.add_argument("--page-budget", type=int, default=None,
                    help="run the paged pool with this many pages instead "
                         "of the deadlock-free worst case — over-pressure "
                         "operation recovered by eviction + preemption "
                         "(min: max_len/block_size + 2)")
    ap.add_argument("--swap", action="store_true",
                    help="swap preempted residencies' filled KV to host "
                         "memory and scatter it back at re-admission "
                         "instead of recomputing the prefill")
    ap.add_argument("--slo-weight", type=float, default=0.0,
                    help="weight of the queue-wait term in the scheduler "
                         "objective: fused horizons and prefill chunks "
                         "are charged wall x (1 + w x class-weighted "
                         "queued requests); 0 disables")
    ap.add_argument("--deadline", type=float, default=None, metavar="S",
                    help="per-request wall-clock deadline in seconds from "
                         "submit; expired requests are shed (queued or "
                         "resident) with reason code 'deadline' instead "
                         "of serving tokens nobody is waiting for")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="admission bound: submissions beyond this queue "
                         "depth fail fast with reason code 'capacity' "
                         "(continuous only)")
    ap.add_argument("--watchdog", action="store_true",
                    help="arm the straggler watchdog around decode-span "
                         "fences: a stalled fence commits its late "
                         "tokens, demotes the span's variant, and counts "
                         "as replica-quarantine evidence")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="inject a reproducible fault storm seeded here "
                         "(device errors / NaN logits / fence stalls at "
                         "planned span coordinates) and print the "
                         "recovery ledger; see epilog")
    ap.add_argument("--fault-storm", type=int, default=8, metavar="N",
                    help="number of faults in the --fault-seed storm")
    ap.add_argument("--mesh", default="1,1", metavar="DP,MP",
                    help="serve device mesh 'dp,mp' (continuous only): mp "
                         "shards params + KV heads within a replica, dp "
                         "runs independent engine replicas behind one "
                         "shared admission queue; '1,1' (default) is the "
                         "bitwise-identical single-device engine.  Multi-"
                         "device CPU needs XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    args = ap.parse_args()
    enable_compile_cache()
    try:
        dp, mp = (int(x) for x in args.mesh.split(","))
    except ValueError:
        ap.error(f"--mesh must be 'dp,mp' integers, got {args.mesh!r}")
    chunk = (args.prefill_chunk if args.prefill_chunk in ("whole", "auto")
             else int(args.prefill_chunk))
    horizon = (args.decode_horizon if args.decode_horizon == "auto"
               else int(args.decode_horizon))
    spec = (args.spec_draft if args.spec_draft in ("off", "auto")
            else int(args.spec_draft))

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    params = init_serve_params(cfg, args.seed, mp if args.continuous else 1)
    rng = np.random.default_rng(args.seed)
    def _prio(i: int) -> str:
        if args.priority == "mix":
            return "interactive" if i % 2 == 0 else "batch"
        return args.priority

    reqs = [Request(
        rid=i,
        prompt=rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32),
        max_new_tokens=args.new_tokens, priority=_prio(i),
        deadline_s=args.deadline)
        for i in range(args.requests)]
    if args.continuous:
        plan = (FaultPlan.seeded(args.fault_seed, args.fault_storm,
                                 slots=args.batch)
                if args.fault_seed is not None else None)
        engine = make_serve_engine(
            cfg, params, mesh_shape=(dp, mp),
            slots=args.batch, max_len=args.max_len, vpe=VPE(),
            prefix_blocks=args.prefix_blocks if args.prefix_cache else 0,
            block_size=args.block_size, kv_layout=args.kv_layout,
            prefill_chunk=chunk, chunks_per_step=args.chunks_per_step,
            decode_horizon=horizon, spec_draft=spec,
            page_budget=args.page_budget,
            swap=args.swap, slo_weight=args.slo_weight,
            decode_impl=args.decode_impl, prefill_kernel=args.prefill_kernel,
            fault_plan=plan, watchdog=args.watchdog,
            max_queue_depth=args.max_queue_depth)
        for r in reqs:
            engine.submit(r)
        done = engine.run()
        mesh_note = f" [mesh {dp}x{mp}]" if (dp, mp) != (1, 1) else ""
        print(f"completed {len(done)} requests{mesh_note}; "
              f"{engine.stats.summary()}")
        if plan is not None:
            fired = ", ".join(f"{f.site}/{f.kind}@{f.at}"
                              for f in plan.injected) or "none"
            print(f"fault storm (seed {args.fault_seed}): "
                  f"{len(plan.injected)}/{len(plan)} fired [{fired}]")
        stats = engine.stats
        if stats.spec_calls:
            hist = ", ".join(f"{k}:{v}" for k, v in
                             sorted(stats.accept_hist.items()))
            print(f"spec accept histogram (drafts accepted -> slot-calls): "
                  f"{hist}")
        return
    if (dp, mp) != (1, 1):
        ap.error("--mesh requires --continuous")
    serve = ServeLoop(cfg, params, max_len=args.max_len, batch=args.batch)
    sched = WaveScheduler(serve)
    for r in reqs:
        sched.submit(r)
    done = sched.run()
    print(f"completed {len(done)} requests; "
          f"decode throughput {serve.stats.decode_tok_per_s:.1f} tok/s "
          f"(prefill {serve.stats.prefill_s:.2f}s, decode {serve.stats.decode_s:.2f}s)")


if __name__ == "__main__":
    main()
