"""Serving cells: the program's ``Engine`` driven by the harness's own
loop (``Engine.submit`` / ``Engine.step``, one process, no threads).

Set-up makes the packed weights on the device from the seed in one
jitted call (``reference/<ref>.py``), builds the ``Engine`` the
configuration describes, runs every program shape the window can reach
through ``submit`` / ``step``, and then pre-rolls the closed loop into
its steady state: the requests that would be in flight once
``2 * max_batch`` had finished are submitted at their progress, so that
lanes are staggered when the window opens. The window then keeps the
queue full for ``seconds`` and counts what the engine emitted. After
it, a sample of the finished requests (drawn from the seed, with the
longest in it) is checked against the plain float32 reference,
teacher-forced over each prompt and its served tokens, once the engine
and its weights are gone.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import time

import numpy as np

import harness
import trafficgen

# seconds of the window's end that a traced run records
TRACE_TAIL_S = 4.0
PROFILE_DIR = harness.ROOT / ".bench_cache" / "profile"


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the arch's
    own, at the file's sizes (a mismatch in what the file cannot change
    is an error)."""
    from repro.configs import get_config
    m, s = conf["model"], conf["sparse_mlp"]
    cfg = get_config(conf["arch"])
    cfg = dataclasses.replace(
        cfg, num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=float(m["rope_theta"]), compute_dtype=m["torch_dtype"],
        blast=dataclasses.replace(cfg.blast, enabled=True,
                                  b_in=s["block"][0], b_out=s["block"][1],
                                  s_init=s["sparsity"],
                                  s_max=s["sparsity"]))
    fixed = {"mlp_kind": "glu", "mlp_act": m["hidden_act"],
             "norm_kind": "layernorm", "qkv_bias": m["use_qkv_bias"],
             "tie_embeddings": m["tie_word_embeddings"], "qk_norm": False,
             "pad_heads_to": 0, "sliding_window": 0,
             "final_logit_softcap": 0.0, "attn_logit_softcap": 0.0,
             "attn_scale": 0.0, "scale_embeddings": False, "is_moe": False}
    bad = {k: (getattr(cfg, k), v) for k, v in fixed.items()
           if getattr(cfg, k) != v}
    if bad:
        raise harness.BenchError(f"{conf['arch']} departs from the "
                                 f"configuration file: {bad}")
    return cfg


def program_params(cfg, glob: dict, layers: dict, d):
    """The reference's weights in the program's parameter tree, checked
    leaf by leaf against the program's own parameter specs."""
    import jax
    from repro.core.packing import PackedBCSC
    from repro.models import registry
    kb_up, kb_dn = d.d_model // d.b_in, d.d_ff // d.b_out
    params = {
        "embed": glob["embed"], "ln_f_scale": glob["ln_f_scale"],
        "ln_f_bias": glob["ln_f_bias"], "lm_head": glob["lm_head"],
        "layers": {
            "ln_attn_scale": layers["ln_attn_scale"],
            "ln_attn_bias": layers["ln_attn_bias"],
            "attn": {k: layers[k] for k in ("wq", "wk", "wv", "wo")},
            "ln_mlp_scale": layers["ln_mlp_scale"],
            "ln_mlp_bias": layers["ln_mlp_bias"],
            "mlp": {
                "w_gate": PackedBCSC(layers["gate_blocks"],
                                     layers["up_idx"], kb_up, joint=True),
                "w_up": PackedBCSC(layers["up_blocks"], layers["up_idx"],
                                   kb_up, joint=True),
                "w_down": PackedBCSC(layers["down_blocks"],
                                     layers["down_idx"], kb_dn)}}}
    want = registry.abstract_params(cfg)
    got = jax.tree_util.tree_map(
        lambda x: (tuple(x.idx.shape[:1]) + x.dense_shape()
                   if isinstance(x, PackedBCSC) else tuple(x.shape)),
        params, is_leaf=lambda x: isinstance(x, PackedBCSC))
    shapes = jax.tree_util.tree_map(lambda s: tuple(s.shape), want)
    if got != shapes:
        raise harness.BenchError("the program's parameter tree differs "
                                 f"from the served layout: {shapes}")
    return params


def pow2_buckets(cap: int) -> list[int]:
    """1, 2, 4, ... up to and including ``cap`` (the engine's buckets)."""
    out, b = [], 1
    while b < cap:
        out.append(b)
        b *= 2
    return out + [cap]


def _pages(slots: int, page: int) -> int:
    return -(-slots // page)


def warm_up(eng, e: dict) -> int:
    """Run every program shape the window can reach, through the public
    ``submit`` / ``step``. The engine reads ``read_pages`` pages, the
    power-of-two bucket of the pages its longest lane needs, and pads a
    mixed step's prefill chunks to a power-of-two query width up to
    ``prefill_chunk``. For each read-page bucket, an anchor request whose
    prompt puts its frontier in that bucket runs a decode slab there;
    then, while it decodes, one probe prompt of each query width runs a
    mixed step beside it. Returns the engine steps run."""
    page, slab_k = e["page_size"], e["slab_k"]
    max_pages = _pages(e["max_len"], page)
    widths = pow2_buckets(1 << max(0, (e["prefill_chunk"] - 1)
                                   .bit_length()))
    steps = 0

    def step():
        nonlocal steps
        eng.step()
        steps += 1

    for r in pow2_buckets(max_pages):
        # the slab needs frontier + slab_k slots, a mixed step frontier
        # + 1: just past the bucket below, both stay in bucket r for
        # the slab and the probes' steps after it
        lo = page * r // 2 if r > 1 else 0
        prompt = max(1, lo + 1 - slab_k)
        slabs = eng.stats["decode_slabs"]
        eng.submit(np.zeros(prompt, np.int32), 1 + slab_k + len(widths))
        while eng.stats["decode_slabs"] == slabs:
            step()
        for w in widths:
            eng.submit(np.zeros(w, np.int32), 1)
            step()
        while _busy(eng):
            step()
    return steps


def staggered_start(pairs, e: dict, finished: int):
    """The closed loop's state once ``finished`` requests have finished,
    from a host model of the engine's documented policy: requests are
    admitted in order while a lane is free and the page pool holds the
    request's whole extent (prompt + output - 1 slots, at most
    ``max_len``), and every admitted request emits one token per step.
    Returns [(list index, tokens already emitted)] of the requests in
    flight, in admission order, and the index of the next request."""
    page, lanes = e["page_size"], e["max_batch"]
    free, nxt, done, live = e["n_pages"], 0, 0, []
    while True:
        while len(live) < lanes:
            p, o = pairs[nxt % len(pairs)]
            need = _pages(min(p + o - 1, e["max_len"]), page)
            if need > free:
                break
            live.append([nxt, 0, o, need])
            free -= need
            nxt += 1
        if done >= finished:
            return [(i, k) for i, k, _, _ in live], nxt
        for lane in live:
            lane[1] += 1
        done += sum(lane[1] >= lane[2] for lane in live)
        free += sum(lane[3] for lane in live if lane[1] >= lane[2])
        live = [lane for lane in live if lane[1] < lane[2]]


def pre_roll(eng, reqs, e: dict, seed: int, vocab: int,
             submitted: dict) -> int:
    """Put the engine in the closed loop's steady state before the
    window: submit the requests in flight at ``staggered_start`` (after
    ``2 * max_batch`` requests finished), each as its prompt followed by
    the tokens it has emitted (drawn from the seed) with the rest of its
    output to come, and step until the engine has prefilled as many
    prompt tokens as they hold. Returns the index of the next request."""
    pairs = [(p.size, n) for p, n in reqs]
    flight, nxt = staggered_start(pairs, e, 2 * e["max_batch"])
    rng = np.random.default_rng([seed, 1])
    todo = 0
    for i, k in flight:
        prompt, new = reqs[i % len(reqs)]
        ctx = np.concatenate([prompt, rng.integers(0, vocab, k,
                                                   dtype=np.int32)])
        uid = eng.submit(ctx, new - k)
        submitted[uid] = (ctx, new - k)
        todo += ctx.size
    done = eng.stats["prefill_tokens"] + todo
    while eng.stats["prefill_tokens"] < done:
        eng.step()
    return nxt


@dataclasses.dataclass
class Window:
    seconds: float
    tokens: float             # emitted in the window (see run_window)
    finished: list            # GenResult of every request done in it
    counters: dict[str, float]  # engine stat deltas over the window
    trace_counters: dict[str, float] | None
    submitted: dict[int, tuple[np.ndarray, int]]


def _counters(eng) -> dict[str, float]:
    """The engine's numeric stats, which the per-layer readers take as
    deltas over the window (``finalize_stats`` documents each)."""
    return {k: v for k, v in eng.stats.items()
            if isinstance(v, (int, float))}


def _delta(a, b):
    return {k: b[k] - a[k] for k in a}


def _busy(eng) -> bool:
    return bool(eng.active_lanes or len(eng.scheduler))


def run_window(eng, reqs, nxt: int, mix: dict, lanes: int,
               seconds: float, trace: bool, compiles,
               submitted: dict) -> Window:
    """A closed loop for ``seconds``: ``queue_per_lane * lanes``
    requests wait at all times, taken in order from ``reqs`` from index
    ``nxt`` on. The engine steps until the window closes; the step in
    progress at the close counts with the share of its tokens that its
    share of time inside the window gives (a slab emits its tokens one
    step of ``slab_k`` at a time). With ``trace``, the profiler records
    the window's last ``TRACE_TAIL_S`` seconds."""
    import jax
    from jax.profiler import TraceAnnotation
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    queue = mix["queue_per_lane"] * lanes

    def top_up():
        nonlocal nxt
        while len(eng.scheduler) < queue:
            prompt, new = reqs[nxt % len(reqs)]
            with TraceAnnotation("Engine.submit"):
                uid = eng.submit(prompt, new)
            submitted[uid] = (prompt, new)
            nxt += 1

    finished, tr_c0, tr_c = [], None, None
    top_up()
    c0 = _counters(eng)
    compiles.on = True
    t0 = time.monotonic()
    deadline = t0 + seconds
    trace_at = deadline - min(TRACE_TAIL_S, seconds) if trace else None
    tracing, tokens = False, 0.0
    while True:
        if trace_at is not None and not tracing and \
                time.monotonic() >= trace_at:
            shutil.rmtree(PROFILE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(PROFILE_DIR))
            win = TraceAnnotation("bench.window")
            win.__enter__()
            tracing, tr_c0 = True, _counters(eng)
        n0, s0 = eng.stats["generated_tokens"], time.monotonic()
        with TraceAnnotation("Engine.step"):
            finished += eng.step()
        s1 = time.monotonic()
        share = (min(1.0, max(0.0, (deadline - s0) / (s1 - s0)))
                 if s1 > s0 else float(s1 < deadline))
        tokens += (eng.stats["generated_tokens"] - n0) * share
        if s1 >= deadline:
            break
        top_up()
    compiles.on = False
    c1 = _counters(eng)
    if tracing:
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
        tr_c = _delta(tr_c0, c1)
    return Window(seconds, tokens, finished, _delta(c0, c1), tr_c,
                  submitted)


def check_results(finished, submitted, vocab: int) -> list:
    """Requests that failed: an error, a truncation, a wrong number of
    tokens or a token outside the vocabulary."""
    bad = []
    for r in finished:
        prompt, new = submitted[r.uid]
        g = np.asarray(r.generated)
        if (r.error is not None or r.truncated or g.size != new
                or not np.array_equal(np.asarray(r.prompt), prompt)
                or (g < 0).any() or (g >= vocab).any()):
            bad.append(r)
    return bad


def sample(finished, n: int, seed: int):
    """``n`` finished requests drawn from the seed, the longest among
    them."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: finished[i].prompt.size
                  + finished[i].generated.size)
    rest = [i for i in range(len(finished)) if i != longest]
    pick = np.random.default_rng(seed).permutation(rest)[:max(n - 1, 0)]
    return [finished[i] for i in [longest, *sorted(pick)]]


def verdict(gaps, bad: list, limit: float) -> list:
    """The numbers compared for ``correct``, each with its limit: the
    widest gap by which a served token's logit lies below the
    reference's best, and the requests that failed."""
    return [("max_served_logit_gap",
             None if gaps is None else float(gaps.max()), limit),
            ("failed_requests", float(len(bad)), 0.0)]


def is_correct(checks) -> bool:
    return all(v is not None and v <= lim for _, v, lim in checks)


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        devices, compiles, *, corrupt=None, control=None
        ) -> tuple[dict, list]:
    """One run of a serving cell. Returns (result, checks). For the
    benchmark's tests only: ``corrupt`` wraps the engine after set-up,
    to plant a fault in the timed path, and ``control`` (a precision of
    the reference, ``"fp8"``) puts the control's first-ranked tokens in
    the served tokens' place for ``correct`` and its checks; the
    program's own checks are then under ``program_checks``."""
    import jax
    from repro.serving.engine import Engine
    conf, mix = cell.config, cell.traffic
    ref = harness.load_module("reference", conf["reference"])
    d = ref.dims_from_config(conf)
    cfg = model_config(conf)
    e = conf["engine"]

    def mark(name):
        marks.append((name, harness.process_age_s(),
                       harness.memory_peak_bytes(devices)))

    marks = []
    mark("start")
    glob, layers = jax.jit(lambda k: ref.all_weights(d, k))(
        ref.seed_key(seed))
    params = program_params(cfg, glob, layers, d)
    del glob, layers
    jax.block_until_ready(params)
    mark("weights")
    eng = Engine(cfg, params, max_batch=e["max_batch"],
                 max_len=e["max_len"], prefill_chunk=e["prefill_chunk"],
                 slab_k=e["slab_k"], eos_id=e["eos_id"], paged=e["paged"],
                 page_size=e["page_size"], n_pages=e["n_pages"],
                 attn_backend=e["attn_backend"],
                 prefix_cache=e["prefix_cache"], mixed=e["mixed"])
    mark("engine")
    shapes = warm_up(eng, e)
    mark("warm-up")
    if corrupt is not None:
        corrupt(eng)
    reqs = trafficgen.requests(mix, seed, d.vocab)
    submitted = {}
    nxt = pre_roll(eng, reqs, e, seed, d.vocab, submitted)
    mark("pre-roll")
    setup_s = harness.process_age_s()
    win = run_window(eng, reqs, nxt, mix, e["max_batch"], seconds, trace,
                     compiles, submitted)
    peak = harness.memory_peak_bytes(devices)
    print("bench: set-up " + ", ".join(
        f"{name} at {t:.3f} s (memory peak {b})" for name, t, b in marks),
        file=sys.stderr, flush=True)
    print(f"bench: set-up {setup_s:.3f} s, {shapes} warm-up steps; "
          f"window {win.seconds:.3f} s, {len(win.finished)} "
          f"requests finished, {win.tokens:.3f} tokens in it "
          f"({win.counters['generated_tokens']} by the steps it began), "
          f"{win.counters['decode_steps']} decode steps, "
          f"{compiles.count} programs lowered in the window; "
          f"memory_peak_bytes {peak}", file=sys.stderr, flush=True)

    bad = check_results(win.finished, win.submitted, d.vocab)
    bad_uids = {r.uid for r in bad}
    picked = sample([r for r in win.finished if r.uid not in bad_uids],
                    conf["correct"]["sample_requests"], seed)
    del eng, params
    gc.collect()
    t_ref = time.monotonic()
    served = [(np.asarray(r.prompt), np.asarray(r.generated))
              for r in picked]
    # the longest sequence the mix can make: one reference program
    pad_to = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    gaps = ref.served_gaps(d, seed, served, pad_to) if picked else None
    print(f"bench: reference over {len(picked)} requests, "
          f"{0 if gaps is None else gaps.size} served tokens, longest "
          f"{max((p.size + g.size for p, g in served), default=0)}, "
          f"{time.monotonic() - t_ref:.1f} s", file=sys.stderr, flush=True)
    limit = conf["correct"]["max_gap_logits"]
    checks = verdict(gaps, bad, limit)
    extra = {}
    if control is not None:
        cgaps = ref.control_gaps(d, seed, served, control, pad_to) \
            if picked else None
        extra["program_checks"] = {n: v for n, v, _ in checks}
        checks = verdict(cgaps, bad, limit)

    result = {"correct": is_correct(checks),
              "attempted": len(win.finished), "failed": len(bad),
              "device": dict(harness.device_info(devices),
                             memory_peak_bytes=peak), **extra}
    if trace:
        import devtrace
        s = devtrace.summarize(devtrace.load(PROFILE_DIR))
        ctx = {"dims": d, "engine": e, "window_s": win.seconds,
               "counters": win.counters,
               "trace_counters": win.trace_counters, "trace": s,
               "peaks": harness.peaks(devices[0].device_kind),
               "chips": len(devices)}
        result["device"].update(busy_s=s.busy_s, window_s=s.window_s)
        result["breakdown"] = devtrace.breakdown(s)
        result["metrics"] = harness.read_per_layer(cell, ctx)
    else:
        values = {"setup_s": setup_s,
                  "output_tok_s": win.tokens / win.seconds}
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in values]
        if missing:
            raise harness.BenchError(f"{cell.name} measures no {missing}")
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    return result, checks
