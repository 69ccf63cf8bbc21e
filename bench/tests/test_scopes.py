"""Device time by named scope on a small synthetic trace laid out as a
v5e profile holds it: op events named by their instruction text inside
an ``XLA Modules`` event, each program's compiled HLO (with every
instruction's ``op_name``) in the ``/host:metadata`` plane. Checked: the
innermost vocabulary scope of an op's path, a compiler-made op and the
layer loop's slicing and stacking counted for an operand's or a
consumer's scope, the ``unscoped`` bucket, clipping to the window,
container ops skipped; and the five readers of the split."""
import pytest
from jax.profiler import ProfileData

import harness
import scopes

US = 1_000_000       # one microsecond in picoseconds
VOCAB = ("embed", "norm", "attn", "attn.qkv", "attn.kv_write",
         "attn.kv_read", "attn.core", "attn.out", "mlp", "lm_head",
         "sample")
PROGRAM = "jit_slab(7)"
# (event name, op_name or None, start us, duration us)
OPS = [
    ("%while.7 = (s32[]) while(%tuple.1), body=%b", "jit(slab)/while",
     10, 90),
    ("%fusion.4 = bf16[4] fusion(%p.1)",
     "jit(slab)/while/body/attn/attn.kv_read/gather", 0, 15),
    ("%dot.1 = f32[2] dot(f32[2] %p.1, f32[2] %q.1)",
     "jit(slab)/while/body/attn/attn.core/dot_general", 15, 20),
    ("%fusion.2 = f32[2] fusion(f32[2] %dot.1)",
     "jit(slab)/while/body/mlp/dot_general", 35, 15),
    # made by the compiler from the kv_read gather: counts for it
    ("%convert.3 = f32[4] convert(bf16[4] %fusion.4)", None, 50, 10),
    # a copy of a loop value: no scope to follow
    ("%copy.5 = bf16[4] copy(bf16[4] %get-tuple-element.9)", None, 60, 10),
    # the loop stacks what the KV write made: counts for its operand
    ("%fusion.6 = bf16[4] fusion(%p.2, %fusion.12)",
     "jit(slab)/while/body/dynamic_update_slice", 70, 5),
    ("%fusion.10 = bf16[4] fusion(%p.3)",
     "jit(slab)/while/body/attn/attn.qkv/mul", 75, 5),
    # the loop slices what the KV write takes: counts for its consumer
    ("%fusion.11 = bf16[4] fusion(%get-tuple-element.2)",
     "jit(slab)/while/body/closed_call/while/body/squeeze", 80, 4),
    ("%fusion.12 = bf16[4] fusion(%fusion.11)",
     "jit(slab)/while/body/attn/attn.kv_write/scatter", 84, 2),
    ("%fusion.8 = bf16[2] fusion(%p.4)", "jit(slab)/lm_head/dot_general",
     105, 15),
]


def _pb(*fields) -> bytes:
    """A serialized protobuf message of (field number, int or bytes)."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def _hlo_proto(ops) -> bytes:
    """An ``xla.HloProto`` whose one computation holds the ops'
    instructions and their op_names."""
    ins = [_pb((1, n[1:].split(" ")[0]), *(
        [(7, _pb((2, op)))] if op else [])) for n, op, *_ in ops]
    return _pb((1, _pb((3, _pb(*[(2, i) for i in ins])))))


def _metadata_plane(ops) -> bytes:
    """The ``/host:metadata`` plane: one program's HLO as an ``Hlo
    Proto`` stat, as an XSpace field to append to a serialized one."""
    stat = _pb((1, 9), (6, _hlo_proto(ops)))
    event = _pb((1, 1), (2, PROGRAM), (5, stat))
    plane = _pb((2, "/host:metadata"), (4, _pb((1, 1), (2, event))),
                (5, _pb((1, 9), (2, _pb((1, 9), (2, "Hlo Proto"))))))
    return _pb((1, plane))


def _xspace(ops=OPS, window=(10, 90)):
    """Device ops in one ``XLA Modules`` event, their op_names in the
    metadata plane; the window annotation on the host."""
    names = list(dict.fromkeys(n for n, *_ in ops)) + [PROGRAM]
    evs = "".join(f" events {{ metadata_id: {names.index(n) + 1} "
                  f"offset_ps: {s * US} duration_ps: {d * US} }}"
                  for n, _, s, d in ops)
    mod = (f' lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 events {{ '
           f'metadata_id: {len(names)} offset_ps: 0 duration_ps: '
           f'{130 * US} }} }}')
    meta = "".join(f' event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{n}" }} }}' for i, n in enumerate(names, 1))
    dev = (f'planes {{ id: 1 name: "/device:TPU:0" lines {{ id: 1 name: '
           f'"XLA Ops" timestamp_ns: 0{evs} }}{mod}{meta} }}')
    s, d = window
    host = (f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 1 name: '
            f'"python" timestamp_ns: 0 events {{ metadata_id: 1 '
            f'offset_ps: {s * US} duration_ps: {d * US} }} }} '
            f'event_metadata {{ key: 1 value {{ id: 1 name: '
            f'"bench.window" }} }} }}')
    return ProfileData.text_proto_to_serialized_xspace(dev + host) + \
        _metadata_plane(ops)


def _seconds(raw, vocab=VOCAB):
    return scopes.from_profile(ProfileData.from_serialized_xspace(raw),
                               vocab, scopes.op_names(raw))


EXPECTED = {
    "attn.kv_read": pytest.approx(15e-6),   # [10, 15) + the convert
    "attn.core": pytest.approx(20e-6),
    "mlp": pytest.approx(15e-6),
    "unscoped": pytest.approx(10e-6),       # the copy
    "attn.qkv": pytest.approx(5e-6),        # innermost, not attn
    "attn.kv_write": pytest.approx(11e-6),  # with the loop's slice and
                                            # stack
    # lm_head's op starts at 105: outside the window
}


def test_op_names_from_the_metadata_plane():
    names = scopes.op_names(_xspace())
    assert names == {PROGRAM: {n[1:].split(" ")[0]: op
                               for n, op, *_ in OPS if op}}


def test_scope_seconds():
    assert _seconds(_xspace()) == EXPECTED


def test_no_scopes_reads_nothing():
    assert _seconds(_xspace(), vocab=()) is None        # the parent
    bare = [(n, None, s, d) for n, _, s, d in OPS]
    assert _seconds(_xspace(bare)) is None


def test_scope_of_takes_the_innermost():
    assert scopes.scope_of("jit(f)/attn/attn.out/dot", VOCAB) == \
        "attn.out"
    assert scopes.scope_of("jit(f)/lm_head/norm/add", VOCAB) == "norm"
    assert scopes.scope_of("jit(f)/while/body/add", VOCAB) is None


@pytest.fixture
def profiled(tmp_path, monkeypatch):
    (tmp_path / "t.xplane.pb").write_bytes(_xspace())
    monkeypatch.setattr(harness.load_module("drivers", "serve"),
                        "PROFILE_DIR", tmp_path)
    monkeypatch.setattr(scopes, "vocabulary", lambda: VOCAB)
    scopes._load.cache_clear()
    yield
    scopes._load.cache_clear()


def _ctx(decode_steps=4, pages_read=64):
    ref = harness.load_module("reference", "decoder")
    d = ref.Dims(layers=2, d_model=256, heads=4, kv_heads=4, head_dim=64,
                 d_ff=512, vocab=512, rope_theta=1e4, eps=1e-5, b_in=128,
                 b_out=128, sparsity=0.5)
    return {"trace": object(), "chips": 1, "dims": d,
            "engine": {"page_size": 16},
            "peaks": {"hbm_bytes_per_s": 1e12},
            "trace_counters": {"decode_steps": decode_steps,
                               "pages_read": pages_read}}


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


def test_readers(profiled):
    ctx = _ctx()
    attn = _read("attn_ms.decode", ctx)
    mlp = _read("mlp_ms.decode", ctx)
    assert attn == pytest.approx(1e3 * 51e-6 / 4)
    assert mlp == pytest.approx(1e3 * 15e-6 / 4)
    assert _read("unscoped_ms.decode", ctx) == pytest.approx(
        1e3 * 10e-6 / 4)
    # attention: 64 pages x 16 positions x (2 K/V x 2 B x 2 layers x 4
    # heads x 64) + q, k, v, o weights (2 B x 2 layers x 256 x 64 x 16)
    # per step; the MLP: 2 layers x (2 x 4 x 1 + 2 x 2) blocks of
    # 128 x 128 in bf16 and their int32 indices, per step
    attn_bytes = 64 * 16 * 2048 + 4 * 1048576
    mlp_bytes = 2 * (2 * 12 * 128 * 128 + 4 * 12)
    assert _read("attn_hbm_share.decode", ctx) == pytest.approx(
        100 * attn_bytes / 4 / (attn * 1e-3 * 1e12))
    assert _read("mlp_hbm_share.decode", ctx) == pytest.approx(
        100 * mlp_bytes / (mlp * 1e-3 * 1e12))


@pytest.mark.parametrize("name", [
    "attn_ms.decode", "mlp_ms.decode", "unscoped_ms.decode",
    "attn_hbm_share.decode", "mlp_hbm_share.decode"])
def test_readers_need_a_traced_decode_step(profiled, name):
    assert _read(name, _ctx(decode_steps=0)) is None
    assert _read(name, dict(_ctx(), trace=None)) is None
