"""Serving launcher: load (or init) a model, prune+pack per BLaST, and
serve greedy generation through the continuous-batching engine
(``serving/engine.py``) — ragged prompt lengths, FIFO admission, lane
reuse. ``--oracle`` falls back to the token-by-token
``serve_loop.generate`` parity path.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b \
        --smoke --prompt-len 16 --new-tokens 32 --batch 4 [--packed] \
        [--max-batch 2] [--ragged] [--prefill-chunk 8]

``--frontdoor`` serves a live multi-tenant trace through the asyncio
production API instead (``serving/frontend.py``): a batch tier queued
up front, interactive requests arriving mid-decode with an SLA
deadline; ``--sla`` orders admission by priority class (with the
anti-starvation aging bound) and ``--preempt`` lets a page-blocked
interactive head preempt batch lanes — their KV pages round-trip
through host RAM (``serving/offload.py``) and decoding resumes at the
saved frontier, never re-prefilling. Prints the per-class TTFT split
and the preemption/offload counters:

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b \
        --smoke --frontdoor --sla --preempt --batch 4 --n-inter 6
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--packed", action="store_true")
    ap.add_argument("--sparsity", type=float, default=0.8,
                    help="one-shot magnitude sparsity when no ckpt")
    ap.add_argument("--max-batch", type=int, default=0,
                    help="engine lanes (default: --batch)")
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--slab-k", type=int, default=8,
                    help="decode steps per jitted slab (host syncs once "
                         "per slab; 1 = per-token baseline)")
    ap.add_argument("--ragged", action="store_true",
                    help="vary prompt lengths across the batch")
    ap.add_argument("--oracle", action="store_true",
                    help="token-by-token serve_loop.generate instead of "
                         "the continuous-batching engine")
    ap.add_argument("--contiguous", action="store_true",
                    help="dense (B, max_len) KV slab instead of the "
                         "paged page-pool cache (parity baseline)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="cache slots per KV pool page (paged mode)")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="KV pool pages (default: contiguous-equivalent "
                         "max_batch * ceil(max_len / page_size))")
    ap.add_argument("--mixed", action="store_true",
                    help="stall-free mixed batching: fuse chunked "
                         "prefill into the decode step under a token "
                         "budget (decode never stalls for admission)")
    ap.add_argument("--prefill-token-budget", type=int, default=0,
                    help="tokens one mixed step may spend (decode "
                         "first, remainder to prefill chunks; 0 = "
                         "engine default max_batch + prefill_chunk)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prefix cache: share prompt-prefix "
                         "KV pages across requests (refcounted, "
                         "copy-on-write boundary pages, LRU eviction; "
                         "paged mode only)")
    ap.add_argument("--frontdoor", action="store_true",
                    help="serve a live interactive+batch trace through "
                         "the asyncio front door (serving/frontend.py) "
                         "and print the per-class TTFT split")
    ap.add_argument("--sla", action="store_true",
                    help="SLA-class admission (SLAScheduler): "
                         "interactive requests jump the batch tier, "
                         "aged batch requests never starve")
    ap.add_argument("--preempt", action="store_true",
                    help="preempt lower-priority lanes for a blocked "
                         "urgent head: KV pages offload to host RAM "
                         "and restore on readmission (no re-prefill)")
    ap.add_argument("--aging-s", type=float, default=30.0,
                    help="anti-starvation aging period (--sla)")
    ap.add_argument("--n-inter", type=int, default=6,
                    help="interactive arrivals in the frontdoor trace")
    ap.add_argument("--inter-tokens", type=int, default=8)
    ap.add_argument("--inter-gap-s", type=float, default=0.5,
                    help="gap between interactive arrivals")
    ap.add_argument("--deadline-s", type=float, default=0.5,
                    help="interactive SLA deadline (EDF within class)")
    ap.add_argument("--trace-out", default=None,
                    help="record request spans (obs/trace.py) and "
                         "write a Chrome/Perfetto trace JSON here — "
                         "open in https://ui.perfetto.dev")
    ap.add_argument("--artifact", default=None, metavar="DIR",
                    help="serve from a SEALED artifact "
                         "(serving/artifact.py): every layer is "
                         "verified — checksums, config fingerprint, "
                         "packed structure, golden canaries — before a "
                         "single token is served; a corrupt artifact "
                         "exits non-zero with the typed error")
    ap.add_argument("--validate-only", action="store_true",
                    help="with --artifact: verify and exit (exit code "
                         "2 + typed error on any corruption)")
    ap.add_argument("--seal", default=None, metavar="DIR",
                    help="pack (requires --packed) and seal the "
                         "serving weights into DIR as a validated "
                         "artifact — config fingerprint, per-array "
                         "crc32s, golden canary generations — then "
                         "exit")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import export

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)

    if args.validate_only and not args.artifact:
        raise SystemExit("--validate-only requires --artifact")
    if args.artifact:
        from repro.serving import artifact as art
        try:
            params, manifest = art.load(args.artifact, cfg,
                                        run_canaries=True)
        except art.ArtifactError as e:
            print(f"artifact INVALID ({type(e).__name__}): {e}")
            raise SystemExit(2)
        print(f"artifact OK: fingerprint "
              f"{manifest['fingerprint'][:12]}…, "
              f"{len(manifest['checksums'])} arrays, "
              f"{len(manifest.get('canaries', []))} canaries replayed")
        if args.validate_only:
            return
        _serve(cfg, params, args)
        return

    params, masks = served_params(cfg, sparsity=args.sparsity,
                                  ckpt_dir=args.ckpt_dir)
    pad_report: dict = {}
    params = (export.pack_params(cfg, params, masks,
                                 pad_report=pad_report)
              if args.packed else export.prune_params(cfg, params, masks))
    print("serving memory:", export.memory_report(cfg, params))

    if args.seal:
        from repro.serving import artifact as art
        if not args.packed:
            raise SystemExit("--seal requires --packed (artifacts hold "
                             "packed serving params)")
        manifest = art.seal(cfg, params, args.seal,
                            pad=pad_report or None)
        print(f"sealed {args.seal}: fingerprint "
              f"{manifest['fingerprint'][:12]}…, "
              f"{len(manifest['checksums'])} arrays, "
              f"{len(manifest['canaries'])} canaries")
        return

    _serve(cfg, params, args)


def served_params(cfg, *, seed: int = 0, sparsity: float = 0.8,
                  ckpt_dir: str | None = None):
    """The dense bf16 serving weights and their BLaST block masks, with
    no optimizer state: restored from ``ckpt_dir``, or made from
    ``seed`` with a one-shot magnitude prune at ``sparsity``. Float
    leaves arrive in bf16 leaf by leaf, so the device never holds a
    float32 copy of the model (stablelm-3b: 5.6 GB in bf16, where the
    float32 params plus AdamW moments are 33.6 GB)."""
    from repro.core import sparse_mlp as sm
    from repro.core.prune_grow import initial_mask
    from repro.models import registry
    from repro.training import step as ts

    if ckpt_dir:
        from repro.checkpointing.checkpoint import Checkpointer
        abstract = ts.abstract_state(cfg)
        tree = Checkpointer(ckpt_dir).restore(
            {"params": abstract.params, "masks": abstract.masks})
        params = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, jnp.bfloat16
                                  if x.dtype == np.float32 else x.dtype),
            tree["params"])
        return params, jax.tree_util.tree_map(jnp.asarray, tree["masks"])
    params = registry.init_params(cfg, jax.random.PRNGKey(seed),
                                  dtype=jnp.bfloat16)
    masks = {}
    for path in (registry.sparse_paths(cfg) if cfg.blast.enabled else []):
        w = sm.get_path(params, path)
        bi, bo = sm.block_dims_for(cfg.blast, path)
        spec = dataclasses.replace(cfg.blast, s_init=sparsity,
                                   s_max=sparsity, b_in=bi, b_out=bo)
        # one layer (expert) at a time: over a whole stack the f32 copy
        # behind the block norms is 2.3 GB per stablelm-3b MLP weight
        fn = lambda wi: initial_mask(spec, wi)
        for _ in range(w.ndim - 2):
            fn = functools.partial(jax.lax.map, fn)
        masks[path] = jax.jit(fn)(w)
    return params, masks


def _serve(cfg, params, args):
    from repro.models import registry
    from repro.serving import engine, serve_loop

    rng = np.random.default_rng(0)
    tracer = None
    if args.trace_out:
        from repro.obs.trace import Tracer
        tracer = Tracer()
    if args.frontdoor:
        if not registry.supports_prefill_chunk(cfg):
            raise SystemExit(f"--frontdoor needs an engine-servable "
                             f"family; {cfg.family!r} is not")
        _frontdoor(cfg, params, args, rng, tracer=tracer)
        _write_trace(args, tracer)
        return
    if args.oracle or not registry.supports_prefill_chunk(cfg):
        prompts = jnp.asarray(rng.integers(
            0, cfg.vocab_size, size=(args.batch, args.prompt_len)),
            jnp.int32)
        toks, stats = serve_loop.generate(cfg, params, prompts,
                                          max_new_tokens=args.new_tokens)
        print(f"generated {toks.shape} — {stats['tok_per_s']:.1f} tok/s")
        print(toks[:, args.prompt_len:][:2])
        return
    lens = (rng.integers(max(1, args.prompt_len // 2),
                         args.prompt_len + 1, size=args.batch)
            if args.ragged else [args.prompt_len] * args.batch)
    prompts = [rng.integers(0, cfg.vocab_size, size=(int(p),))
               .astype(np.int32) for p in lens]
    toks, stats = engine.generate(
        cfg, params, prompts, max_new_tokens=args.new_tokens,
        max_batch=args.max_batch or args.batch,
        prefill_chunk=args.prefill_chunk, slab_k=args.slab_k,
        paged=not args.contiguous, page_size=args.page_size,
        n_pages=args.n_pages or None, prefix_cache=args.prefix_cache,
        mixed=args.mixed,
        prefill_token_budget=args.prefill_token_budget or None,
        tracer=tracer)
    print(f"generated {len(toks)} seqs — {stats['tok_per_s']:.1f} tok/s "
          f"({stats['decode_slabs']} slabs of {args.slab_k}, "
          f"{stats['prefill_chunks']} prefill chunks, "
          f"peak_kv_kib={stats['peak_kv_bytes'] / 1024:.1f}, "
          f"ttft_p95_ms={stats['ttft_p95_s'] * 1e3:.1f})"
          + (f" prefix_hit_rate={stats['prefix_hit_rate']:.2f} "
             f"skipped={stats['prefill_tokens_skipped']}"
             if args.prefix_cache else "")
          + (f" mixed_steps={stats['mixed_steps']} "
             f"stalled={stats['stalled_decode_steps']}"
             if args.mixed else ""))
    for p, t in list(zip(prompts, toks))[:2]:
        print(t[p.size:])
    mem = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in mem:
        print(f"peak device bytes: {mem['peak_bytes_in_use']:,}")
    _write_trace(args, tracer)


def _write_trace(args, tracer):
    if tracer is None:
        return
    from repro.obs.export import write_chrome_trace
    write_chrome_trace(args.trace_out, tracer.records,
                       offset_s=tracer.clock_offset)
    print(f"wrote {len(tracer.records)} spans to {args.trace_out} "
          f"(open in https://ui.perfetto.dev)")


def _frontdoor(cfg, params, args, rng, tracer=None):
    """The asyncio front door over a live multi-tenant trace: batch
    jobs saturate the lanes, interactive requests trickle in and (with
    --sla / --preempt) jump the queue or preempt a batch lane's KV to
    host. Streams are consumed concurrently; per-class TTFT is measured
    from each request's own submission."""
    from repro.serving.engine import Engine
    from repro.serving.frontend import AsyncEngine
    from repro.serving.scheduler import (BATCH, INTERACTIVE,
                                         FIFOScheduler, SLAScheduler)

    max_batch = args.max_batch or 2
    max_len = max(args.prompt_len + args.new_tokens + 8, 32)

    def build():
        sched = (SLAScheduler(max_batch, max_len, aging_s=args.aging_s)
                 if args.sla else FIFOScheduler(max_batch, max_len))
        return Engine(cfg, params, max_batch=max_batch, max_len=max_len,
                      prefill_chunk=args.prefill_chunk,
                      slab_k=args.slab_k, page_size=args.page_size,
                      n_pages=args.n_pages or None, scheduler=sched,
                      mixed=args.mixed, preempt=args.preempt,
                      tracer=tracer)

    # jit-warm both request shapes outside the served trace
    warm = build()
    warm.submit(np.ones(args.prompt_len, np.int32), 4, priority=BATCH)
    warm.submit(np.ones(max(args.prompt_len // 2, 1), np.int32), 4,
                priority=INTERACTIVE)
    warm.run()

    eng = build()
    lat = {"batch": [], "interactive": []}

    async def one(front, prompt, tokens, klass, *, delay=0.0, **kw):
        """One client: wait for its arrival time, submit, stream.
        TTFT is measured from BEFORE the submit — ack latency (the
        engine thread drains its inbox between steps) and queue wait
        both count, as a served client would experience them."""
        await asyncio.sleep(delay)
        t0 = time.monotonic()
        stream = await front.submit_async(prompt, tokens, **kw)
        first = None
        async for _ in stream:
            if first is None:
                first = time.monotonic() - t0
        await stream.result()
        lat[klass].append((first, time.monotonic() - t0))

    async def drive():
        async with AsyncEngine(eng) as front:
            tasks = []
            for _ in range(args.batch):
                p = rng.integers(0, cfg.vocab_size, args.prompt_len)
                tasks.append(one(front, p.astype(np.int32),
                                 args.new_tokens, "batch",
                                 priority=BATCH))
            for k in range(args.n_inter):
                p = rng.integers(0, cfg.vocab_size,
                                 max(args.prompt_len // 2, 1))
                tasks.append(one(front, p.astype(np.int32),
                                 args.inter_tokens, "interactive",
                                 delay=(k + 1) * args.inter_gap_s,
                                 priority=INTERACTIVE,
                                 deadline_s=args.deadline_s))
            await asyncio.gather(*tasks)

    asyncio.run(drive())
    for klass in ("interactive", "batch"):
        ttft = np.array([t for t, _ in lat[klass]])
        e2e = np.array([e for _, e in lat[klass]])
        print(f"{klass:>12}: n={len(ttft)} "
              f"ttft p50={np.percentile(ttft, 50) * 1e3:7.1f}ms "
              f"p95={np.percentile(ttft, 95) * 1e3:7.1f}ms   "
              f"e2e p95={np.percentile(e2e, 95) * 1e3:7.1f}ms")
    st = eng.stats
    print(f"{'engine':>12}: {st['e2e_tok_per_s']:.1f} tok/s e2e, "
          f"preemptions={st['preemptions']} restores={st['restores']} "
          f"offloaded_pages={st['offloaded_pages']} "
          f"offload_bytes_peak={st['offload_bytes_peak']:,} "
          f"stalled_decode_steps={st['stalled_decode_steps']}")


if __name__ == "__main__":
    main()
