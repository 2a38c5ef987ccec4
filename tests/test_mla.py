"""Multi-head latent attention on the compressed cache: the absorbed decode
against the plain float32 reference, YaRN against its formula, the latent
cache's flush groups and bulk prefill, the byte counts, and a session's
prefill then decode against the reference forward."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gbdi_fr import FRConfig, fit_fr_bases
from repro.kernels import ops
from repro.models import mla
from repro.serving import kv_cache as kvc
from repro.serving.engine import KVSession

SMALL = mla.MLAConfig(hidden_size=256, num_heads=8, q_lora_rank=64, kv_lora_rank=64,
                      qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32)
# 80-word latent rows on 128-word pages: a flush group is 8 tokens in 5 pages
FR = FRConfig(word_bits=16, page_words=128, num_bases=14, width_set=(8,),
              bucket_caps=(128,), outlier_cap=16)
B = 2


def _words(x):
    return jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16), jnp.uint16).astype(jnp.int32)


def _bf16(w):
    return jax.lax.bitcast_convert_type(w.astype(jnp.uint16), jnp.bfloat16)


def _bit_equal(a, b, msg=""):
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype == jnp.bfloat16:
        a, b = a.view(np.uint16), b.view(np.uint16)
    np.testing.assert_array_equal(a, b, err_msg=msg)


def _table(rows):
    return fit_fr_bases(_words(rows).reshape(-1), FR)


def _roundtrip(rows, table):
    """bf16 rows (B, T, R), T whole groups -> what the oracle codec gives back."""
    w = _words(rows).reshape(-1, FR.page_words)
    back = ops.decode_pages(ops.encode_pages(w, table, FR, backend="ref"), table, FR,
                            backend="ref")
    return _bf16(back).reshape(rows.shape)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T", [1, 9, 24])
def test_absorbed_decode_matches_reference(T):
    """Token by token through a raw float32 latent cache, the absorbed step
    gives the non-absorbed reference forward: they differ only in the order
    of float32 products (W_UK and W_UV applied to the query and output
    instead of to every key and value)."""
    p = mla.init(jax.random.PRNGKey(T), SMALL, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(100 + T), (B, T, SMALL.hidden_size))
    ref = np.asarray(mla.reference_forward(p, SMALL, x))
    rows = jnp.zeros((B, T, SMALL.row_words), jnp.float32)
    scale = mla.softmax_scale(SMALL)
    for t in range(T):
        row, q = mla.decode_in(p, SMALL, x[:, t:t + 1], jnp.int32(t))
        assert row.shape == (B, 1, 80) and q.shape == (B, 1, 8, 80)
        rows = rows.at[:, t].set(row[:, 0])
        acc, _, l = mla.latent_attention(q, rows, jnp.arange(T) <= t, scale,
                                         SMALL.kv_lora_rank)
        o = acc / l[..., None]
        y = np.asarray(mla.decode_out(p, SMALL, o))[:, 0]
        np.testing.assert_allclose(y, ref[:, t], atol=1e-4 * np.abs(ref).max(), rtol=0)


def _yarn_by_hand(dim, base, factor, orig, fast, slow):
    freqs = [1.0 / base ** (2 * i / dim) for i in range(dim // 2)]
    cdim = [dim * math.log(orig / (r * 2 * math.pi)) / (2 * math.log(base)) for r in (fast, slow)]
    lo, hi = max(math.floor(cdim[0]), 0), min(math.ceil(cdim[1]), dim - 1)
    out = []
    for i, f in enumerate(freqs):
        keep = 1.0 - min(max((i - lo) / (hi - lo if hi > lo else 0.001), 0.0), 1.0)
        out.append(f / factor * (1.0 - keep) + f * keep)
    return out


@pytest.mark.parametrize("case", [
    # DeepSeek-V3 as published: rope 64, theta 1e4, factor 40 on 4096, mscale 1
    dict(cfg=mla.MLAConfig(), mscale=0.1 * math.log(40) + 1),
    # DeepSeek-V2's mscale_all_dim 0.707
    dict(cfg=mla.MLAConfig(hidden_size=5120, mscale_all_dim=0.707),
         mscale=0.1 * 0.707 * math.log(40) + 1),
    # no scaling: plain RoPE frequencies, unit mscale
    dict(cfg=mla.MLAConfig(rope_factor=1.0), mscale=1.0),
    dict(cfg=SMALL, mscale=0.1 * math.log(40) + 1),
])
def test_yarn_against_its_formula(case):
    cfg = case["cfg"]
    want = _yarn_by_hand(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
                         cfg.original_max_position, cfg.beta_fast, cfg.beta_slow)
    np.testing.assert_allclose(mla.yarn_inv_freq(cfg), want, rtol=1e-6)
    assert mla.softmax_scale(cfg) == pytest.approx(
        (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * case["mscale"] ** 2, rel=1e-12)
    if cfg.rope_factor == 1.0:
        np.testing.assert_allclose(mla.yarn_inv_freq(cfg),
                                   [10000.0 ** (-2 * i / 64) for i in range(32)], rtol=1e-6)


def test_config_from_the_published_file():
    published = {"hidden_size": 7168, "num_attention_heads": 128, "q_lora_rank": 1536,
                 "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "rope_theta": 10000, "rms_norm_eps": 1e-06,
                 "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                                  "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                                  "type": "yarn"}}
    cfg = mla.MLAConfig.from_hf(published)
    assert cfg == mla.MLAConfig() and cfg.row_words == 576 and cfg.qk_head_dim == 192


# ---------------------------------------------------------------------------
# the latent cache
# ---------------------------------------------------------------------------

def _latent_spec(max_len, resident=True):
    return kvc.LatentSpec(latent_dim=64, rope_dim=16, max_len=max_len, fr=FR,
                          resident_decode=resident)


def _rows(key, lead, n, width):
    # per-channel means plus token noise, as latent rows look after kv_norm
    km, kn = jax.random.split(jax.random.PRNGKey(key))
    mean = jax.random.normal(km, (width,))
    return (mean + 0.3 * jax.random.normal(kn, (*lead, n, width))).astype(jnp.bfloat16)


def test_latent_geometry():
    spec = _latent_spec(20)
    assert (spec.group_tokens, spec.group_pages, spec.n_groups, spec.n_slots) == (8, 5, 3, 15)
    full = kvc.LatentSpec(latent_dim=512, rope_dim=64, max_len=33024)
    assert (full.group_tokens, full.group_pages, full.n_slots) == (32, 9, 1032 * 9)
    seven = kvc.KVSpec(n_kv=32, head_dim=128, max_len=4096)
    assert (seven.group_tokens, seven.group_pages, seven.n_slots) == (1, 2, 8192)


@pytest.mark.parametrize("n", [7, 8, 9, 17])
def test_latent_append_across_group_boundary(n):
    """Appends across the 8-token group boundary: the resident region stays
    bit-identical to a from-scratch decode of the page slots, flushed
    groups read back as the oracle codec's round trip and the unflushed
    tail raw."""
    spec = _latent_spec(24)
    rows = _rows(n, (B,), 24, 80)
    table = _table(rows)
    cache = kvc.init_compressed(spec, B, table)
    append = jax.jit(lambda c, r, t: kvc.append_rows(spec, c, {"c": r}, t))
    for t in range(n):
        cache = append(cache, rows[:, t:t + 1], jnp.int32(t))
    _bit_equal(cache["c_dec"], kvc._decompress_all(spec, cache["c_pages"], table),
               "resident region != from-scratch decode")
    C, valid = kvc.read_full(spec, cache, jnp.int32(n - 1))
    assert C.shape == (B, 24, 80) and int(valid.sum()) == n
    flushed = n // 8 * 8
    if flushed:
        _bit_equal(C[:, :flushed], _roundtrip(rows[:, :flushed], table), "flushed groups")
    _bit_equal(C[:, flushed:n], rows[:, flushed:n], "tail")
    assert cache["c_dropped"].shape == (B,)


@pytest.mark.parametrize("positions", [(0, 16), (4, 20), (7, 23)],
                         ids=["first", "middle", "last"])
def test_latent_attention_masks_at_group_boundaries(positions):
    """The latent cache's decode attention reads the region's flushed
    positions and the raw tail as two softmax segments: on a group's first,
    a middle and its last slot it gives a float32 softmax over the full
    [0, pos] view, and rows of the region at and past the current group's
    start (stale rows, as after a restart) change no bit of the output."""
    spec = _latent_spec(24)
    n = max(positions) + 1
    rows = _rows(n, (B,), n, 80)
    table = _table(rows)
    qs = jax.random.normal(jax.random.PRNGKey(n), (n, B, 1, 4, 80))
    scale = 80 ** -0.5
    append = jax.jit(lambda c, r, t: kvc.append_rows(spec, c, {"c": r}, t))
    cache = kvc.init_compressed(spec, B, table)
    for t in range(n):
        cache = append(cache, rows[:, t:t + 1], jnp.int32(t))
        if t not in positions:
            continue
        pos = jnp.int32(t)
        C = np.asarray(kvc.read_full(spec, cache, pos)[0], np.float32)[:, :t + 1]
        lg = np.einsum("bqhc,btc->bqht", np.asarray(qs[t]), C) * np.float32(scale)
        p = np.exp(lg - lg.max(-1, keepdims=True))
        want = np.einsum("bqht,btc->bqhc", p / p.sum(-1, keepdims=True), C[..., :64])
        out = kvc.attention_decode_latent(spec, qs[t], cache, pos, scale)
        assert out.shape == (B, 1, 4, 64) and out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32), want, atol=2e-2,
                                   err_msg=f"pos {t}")
        base = t // spec.group_tokens * spec.group_tokens
        stale = {**cache, "c_dec": cache["c_dec"].at[:, base:].set(1e6)}
        _bit_equal(kvc.attention_decode_latent(spec, qs[t], stale, pos, scale), out,
                   f"stale rows @ pos {t}")


@pytest.mark.parametrize("geometry", ["latent", "kv"])
def test_bulk_prefill_matches_appends(geometry):
    """Whole groups written by prefill_groups (one group, then two more from
    a non-zero start) give the same cache tree, leaf for leaf and bit for
    bit, as the same tokens appended one at a time."""
    if geometry == "latent":
        spec = _latent_spec(40)
        streams = {"c": _rows(1, (B,), 24, 80)}
    else:
        spec = kvc.KVSpec(n_kv=2, head_dim=16, max_len=16, fr=FR, resident_decode=True)
        streams = {"k": _rows(2, (B,), 12, 32).reshape(B, 12, 2, 16),
                   "v": _rows(3, (B,), 12, 32).reshape(B, 12, 2, 16)}
    G = spec.group_tokens
    table = _table(jnp.concatenate([r.reshape(-1) for r in streams.values()]))
    bulk = kvc.init_compressed(spec, B, table)
    prefill = jax.jit(lambda c, rows, s: kvc.prefill_groups(spec, c, rows, s))
    bulk = prefill(bulk, {s: r[:, :G] for s, r in streams.items()}, jnp.int32(0))
    bulk = prefill(bulk, {s: r[:, G:3 * G] for s, r in streams.items()}, jnp.int32(G))
    one = kvc.init_compressed(spec, B, table)
    append = jax.jit(lambda c, rows, t: kvc.append_rows(spec, c, rows, t))
    for t in range(3 * G):
        one = append(one, {s: r[:, t:t + 1] for s, r in streams.items()}, jnp.int32(t))
    for key in one:
        if key != "table":
            jax.tree.map(lambda a, b: _bit_equal(a, b, key), bulk[key], one[key])
    with pytest.raises(ValueError, match="whole groups"):
        kvc.prefill_groups(spec, bulk, {s: r[:, :G + 1] for s, r in streams.items()},
                           jnp.int32(0))


@pytest.mark.parametrize("spec", [
    kvc.KVSpec(n_kv=32, head_dim=128, max_len=64),                       # 4096-word rows
    kvc.KVSpec(n_kv=32, head_dim=128, max_len=64, resident_decode=True),
    kvc.LatentSpec(latent_dim=512, rope_dim=64, max_len=96),             # 576-word rows
    kvc.LatentSpec(latent_dim=512, rope_dim=64, max_len=100, resident_decode=True),
    kvc.KVSpec(n_kv=2, head_dim=16, max_len=30, fr=FR, resident_decode=True),
], ids=["kv4096", "kv4096-resident", "latent576", "latent576-resident", "kv32-resident"])
def test_byte_counts_are_the_allocated_leaves(spec):
    """compressed_bytes and compressed_bytes_upto equal the bytes of the
    leaves init_compressed allocates (the shared table aside), and
    raw_bytes those of a raw bf16 cache of the same rows."""
    table = _table(jnp.ones(8))

    def allocated(s, batch):
        tree = jax.eval_shape(lambda: kvc.init_compressed(s, batch, table))
        tree.pop("table")
        return sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(tree))

    assert spec.compressed_bytes(3) == allocated(spec, 3)
    assert spec.raw_bytes(3) == len(spec.streams) * 3 * spec.max_len * spec.row_words * 2
    G = spec.group_tokens
    for n in (0, G - 1, G, 2 * G + 1, spec.max_len):
        groups = min(spec.n_groups, n // G)
        assert spec.compressed_bytes_upto(2, n) == allocated(
            dataclasses.replace(spec, max_len=groups * G), 2)


# ---------------------------------------------------------------------------
# the session: prefill, then decode, against the reference forward
# ---------------------------------------------------------------------------

def test_session_prefill_then_decode_matches_reference():
    """Two MLA layers behind KVSession: a 16-token context bulk-prefilled
    from the program's latent rows, then 12 decode steps across a group
    boundary.  Each step's output of each layer is compared with the float32
    non-absorbed reference attending over the oracle codec's round trip of
    the reference's own latent rows.  The program runs in bf16, so the gap
    allowed is 2% of the output's largest magnitude: a few bf16 roundings
    (2**-8 each) in the projections and the probabilities."""
    L, T0, S = 2, 16, 12
    spec = _latent_spec(32)
    params = [mla.init(jax.random.PRNGKey(10 + i), SMALL) for i in range(L)]
    x_ctx = jax.random.normal(jax.random.PRNGKey(1), (L, B, T0, 256)).astype(jnp.bfloat16)
    x_steps = jax.random.normal(jax.random.PRNGKey(2), (S, L, B, 1, 256)).astype(jnp.bfloat16)
    rows = jnp.stack([mla.latent_rows(params[i], SMALL, x_ctx[i], jnp.arange(T0))
                      for i in range(L)])
    tables = [_table(rows[i]) for i in range(L)]
    sess = KVSession(spec, B, tables, layers=(SMALL, params))
    sess.prefill(rows)
    assert sess.pos == T0
    outs, attns = zip(*[sess.step(x_steps[i]) for i in range(S)])
    assert outs[0].shape == (L, B, 1, 256) and sess.pos == T0 + S
    assert attns[0].shape == (L, B, 1, SMALL.num_heads, SMALL.kv_lora_rank)
    pos = jnp.arange(32)
    for i in range(L):
        x_all = jnp.concatenate([x_ctx[i], jnp.swapaxes(x_steps[:, i, :, 0], 0, 1),
                                 jnp.zeros((B, 32 - T0 - S, 256), jnp.bfloat16)], axis=1)
        ref_rows = _roundtrip(mla.reference_latent(params[i], SMALL, x_all, pos), tables[i])
        ref = np.asarray(mla.reference_attend(params[i], SMALL, x_all[:, T0:T0 + S],
                                              pos[T0:T0 + S], ref_rows))
        got = np.stack([np.asarray(o[i, :, 0], np.float32) for o in outs], axis=1)
        gap = np.abs(got - ref).max() / np.abs(ref).max()
        assert gap < 0.02, (i, gap)


@pytest.mark.parametrize("entry", ["step", "prefill"])
def test_session_programs_carry_the_scopes(entry):
    """The MLA step's and the bulk prefill's op metadata name their phases."""
    from repro import obs

    spec = _latent_spec(16)
    params = [mla.init(jax.random.PRNGKey(0), SMALL)]
    sess = KVSession(spec, 1, [_table(jnp.ones(8))], layers=(SMALL, params))
    if entry == "step":
        fn, args = sess._step, (params, sess.cache, jnp.zeros((1, 1, 1, 256), jnp.bfloat16),
                                jnp.int32(7))
        want = {obs.MLA_Q_PROJ, obs.MLA_KV_PROJ, obs.MLA_ABSORB, obs.MLA_OUT_PROJ,
                obs.KV_ATTEND, obs.KV_FLUSH_ENCODE, obs.KV_FLUSH_DECODE}
    else:
        fn, args = sess._prefill, (sess.cache, jnp.zeros((1, 1, 8, 80), jnp.bfloat16),
                                   jnp.int32(0))
        want = {obs.KV_FLUSH_ENCODE, obs.KV_FLUSH_DECODE}
    text = fn.lower(*args).as_text(debug_info=True)
    assert {name for name in want if f"{name}/" in text} == want


# ---------------------------------------------------------------------------
# the session's ownership of its cache: donated when its own, copied when lent
# ---------------------------------------------------------------------------

def _kv_session():
    """One K/V layer (32-word rows: a flush group of 4 tokens in 1 page),
    8 tokens prefilled, its base table and its steps' inputs."""
    spec = kvc.KVSpec(n_kv=2, head_dim=16, max_len=32, fr=FR, resident_decode=True)
    ks, vs = (jax.random.normal(jax.random.PRNGKey(i), (B, 20, 2, 16)).astype(jnp.bfloat16)
              for i in (3, 4))
    qs = jax.random.normal(jax.random.PRNGKey(5), (12, B, 1, 4, 16)).astype(jnp.bfloat16)
    table = _table(jnp.concatenate([ks, vs], axis=1))
    sess = KVSession(spec, B, table)
    sess.prefill(ks[:, :8], vs[:, :8])
    return sess, table, 8, [(qs[i], ks[:, 8 + i:9 + i], vs[:, 8 + i:9 + i]) for i in range(12)]


def _mla_session():
    """Two MLA layers (a flush group of 8 tokens), 16 tokens prefilled,
    their base tables and the steps' inputs."""
    L, T0 = 2, 16
    params = [mla.init(jax.random.PRNGKey(20 + i), SMALL) for i in range(L)]
    x_ctx = jax.random.normal(jax.random.PRNGKey(6), (L, B, T0, 256)).astype(jnp.bfloat16)
    xs = jax.random.normal(jax.random.PRNGKey(7), (12, L, B, 1, 256)).astype(jnp.bfloat16)
    rows = jnp.stack([mla.latent_rows(params[i], SMALL, x_ctx[i], jnp.arange(T0))
                      for i in range(L)])
    tables = [_table(rows[i]) for i in range(L)]
    sess = KVSession(_latent_spec(32), B, tables, layers=(SMALL, params))
    sess.prefill(rows)
    return sess, tables, T0, [(xs[i],) for i in range(12)]


@pytest.mark.parametrize("kind", ["kv", "mla"])
def test_session_donates_only_the_cache_it_owns(kind):
    """A step on a cache the session made deletes the tree it consumed; a
    tree read through ``cache`` or assigned to it, and the base tables the
    session was given, are never deleted; restarts from the saved
    post-prefill tree give the first pass's outputs bit for bit, at one
    copy of the saved tree each, and build no program once the first pass
    has run.  Each pass of 12 steps crosses a flush."""
    sess, tables, T0, steps = _kv_session() if kind == "kv" else _mla_session()
    assert sess.cache_copies == 0                    # prefill updated the session's own
    post = sess.cache
    passes, held, built = [], [], []
    listen = lambda event, duration, **kw: built.append(event) \
        if event == "/jax/core/compile/backend_compile_duration" else None  # noqa: E731
    for n in range(3):
        if n == 1:                                   # warm: no restart or read compiles
            jax.monitoring.register_event_duration_secs_listener(listen)
        sess.cache, sess.pos = post, T0
        outs = []
        for i, inp in enumerate(steps):
            read = n == 2 and i == 5
            if read:
                held.append(sess.cache)              # a tree read mid-pass
            before = jax.tree.leaves(sess._cache)
            outs.append(jax.tree.leaves(sess.step(*inp)))
            if i > 0 and not read:                   # the session's own tree was donated
                assert all(x.is_deleted() for x in before), (n, i)
        passes.append(outs)
        assert sess.cache_copies == n + 1 + len(held)
    jax.monitoring.unregister_event_duration_listener(listen)
    assert not built
    for tree in (post, *held, tables):
        assert not any(x.is_deleted() for x in jax.tree.leaves(tree))
    for n in (1, 2):
        for i, (a, b) in enumerate(zip(passes[0], passes[n])):
            for x, y in zip(a, b):
                _bit_equal(x, y, f"pass {n}, step {i}")
