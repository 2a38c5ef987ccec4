"""Compressed KV cache: append/read vs raw reference; fused paged-attention
kernel vs oracle; softmax-merge identity."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core.gbdi_fr import FRConfig, fit_fr_bases
from repro.kernels.gbdi_paged_attn import merge_softmax, paged_attention_decode
from repro.serving import kv_cache as kvc

KV, HD, B = 4, 32, 2
# v2 multi-width: narrow class spills bit-exactly into the full-page wide
# bucket, so the tiny test pages keep v1 quality
SPEC = kvc.KVSpec(n_kv=KV, head_dim=HD, max_len=64,
                  fr=FRConfig(word_bits=16, page_words=128, width_set=(4, 8),
                              bucket_caps=(32, 128), num_bases=14, outlier_cap=16))


def _mk_kv(rng, n):
    # channel-structured keys (realistic: per-channel means)
    ch = rng.normal(0, 1, (1, 1, KV, HD)) * 2
    return (ch + rng.normal(0, 0.1, (B, n, KV, HD))).astype(np.float32)


def _bases(sample):
    w = jax.lax.bitcast_convert_type(jnp.asarray(sample).astype(jnp.bfloat16), jnp.uint16)
    return fit_fr_bases(w.astype(jnp.int32).reshape(-1), SPEC.fr)


def test_append_read_matches_raw():
    n = 16  # compression quality is per-token; length only costs wall-clock
    rng = np.random.default_rng(0)
    ks, vs = _mk_kv(rng, n), _mk_kv(rng, n)
    bases = _bases(ks)
    cache = kvc.init_compressed(SPEC, B, bases)
    for t in range(n):
        cache = kvc.append(SPEC, cache, jnp.asarray(ks[:, t:t+1]), jnp.asarray(vs[:, t:t+1]), jnp.int32(t))
    K, V, valid = kvc.read_full(SPEC, cache, jnp.int32(n - 1))
    assert bool(valid[:n].all()) and not bool(valid[n:].any())
    ref = jnp.asarray(ks[:, :n]).astype(jnp.bfloat16).astype(jnp.float32)
    got = K[:, :n].astype(jnp.float32)
    # near-lossless: only dropped outliers differ
    frac = float(jnp.mean((got == ref).astype(jnp.float32)))
    assert frac > 0.98, frac
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=0.25)


def test_adaptive_profile_spec_roundtrips_through_cache():
    """A KVSpec with adaptive cap_profiles carries per-page profile ids in
    the cache tree and reads back with the same quality as static caps."""
    spec = kvc.KVSpec(
        n_kv=KV, head_dim=HD, max_len=64,
        fr=FRConfig(word_bits=16, page_words=128, width_set=(4, 8),
                    cap_profiles=((32, 128), (96, 32)), num_bases=14,
                    outlier_cap=16))
    n = 8
    rng = np.random.default_rng(4)
    ks, vs = _mk_kv(rng, n), _mk_kv(rng, n)
    w = jax.lax.bitcast_convert_type(jnp.asarray(ks).astype(jnp.bfloat16), jnp.uint16)
    table = fit_fr_bases(w.astype(jnp.int32).reshape(-1), spec.fr)
    cache = kvc.init_compressed(spec, B, table)
    assert "profile" in cache["k_pages"]          # adaptive id in the tree
    for t in range(n):
        cache = kvc.append(spec, cache, jnp.asarray(ks[:, t:t+1]),
                           jnp.asarray(vs[:, t:t+1]), jnp.int32(t))
    K, V, valid = kvc.read_full(spec, cache, jnp.int32(n - 1))
    assert bool(valid[:n].all())
    ref = jnp.asarray(ks[:, :n]).astype(jnp.bfloat16).astype(jnp.float32)
    frac = float(jnp.mean((K[:, :n].astype(jnp.float32) == ref).astype(jnp.float32)))
    assert frac > 0.98, frac


def test_compressed_attention_close_to_raw():
    rng = np.random.default_rng(1)
    n = 24
    ks, vs = _mk_kv(rng, n), _mk_kv(rng, n)
    bases = _bases(np.concatenate([ks, vs], axis=1))
    cache = kvc.init_compressed(SPEC, B, bases)
    for t in range(n):
        cache = kvc.append(SPEC, cache, jnp.asarray(ks[:, t:t+1]), jnp.asarray(vs[:, t:t+1]), jnp.int32(t))
    H = 8
    q = rng.normal(0, 1, (B, 1, H, HD)).astype(np.float32)
    out_c = kvc.attention_decode(SPEC, jnp.asarray(q), cache, jnp.int32(n - 1))

    # raw reference
    Kr = jnp.asarray(ks[:, :n]).astype(jnp.bfloat16)
    Vr = jnp.asarray(vs[:, :n]).astype(jnp.bfloat16)
    qg = jnp.asarray(q).reshape(B, 1, KV, H // KV, HD)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, Kr).astype(jnp.float32) / np.sqrt(HD)
    probs = jax.nn.softmax(logits, axis=-1).astype(Vr.dtype)
    ref = jnp.einsum("bkgst,btkh->bskgh", probs, Vr).reshape(B, 1, H * HD)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(ref), atol=0.08, rtol=0.1)


def test_paged_attention_kernel_vs_oracle():
    rng = np.random.default_rng(2)
    n = 24                                 # 24 tokens, page_tokens = 1
    ks, vs = _mk_kv(rng, n), _mk_kv(rng, n)
    bases = _bases(np.concatenate([ks, vs], axis=1))
    cache = kvc.init_compressed(SPEC, B, bases)
    for t in range(n):
        cache = kvc.append(SPEC, cache, jnp.asarray(ks[:, t:t+1]), jnp.asarray(vs[:, t:t+1]), jnp.int32(t))
    H = 8
    G = H // KV
    pos = jnp.int32(n - 1)
    q = rng.normal(0, 1, (B, 1, H, HD)).astype(np.float32)
    qg = jnp.asarray(q).reshape(B, KV, G, HD)

    acc, m, l = paged_attention_decode(
        qg, cache["k_pages"], cache["v_pages"], cache["table"], pos, SPEC.fr,
        n_kv=KV, hd=HD, groups=G, interpret=True,
    )
    # tail stream (the current partial page) via the oracle read
    pt = SPEC.group_tokens
    lim = (int(pos) // pt) * pt
    Kt = cache["k_tail"].astype(jnp.float32)
    Vt = cache["v_tail"].astype(jnp.float32)
    tail_valid = (lim + jnp.arange(pt)) <= pos
    lg = jnp.einsum("bkgh,btkh->bkgt", qg, Kt) / np.sqrt(HD)
    lg = jnp.where(tail_valid[None, None, None, :], lg, -1e30)
    m2 = lg.max(-1)
    p2 = jnp.exp(lg - m2[..., None])
    l2 = p2.sum(-1)
    acc2 = jnp.einsum("bkgt,btkh->bkgh", p2, Vt)
    accm, mm, lm = merge_softmax(acc, m, l, acc2, m2, l2)
    out_kernel = (accm / lm[..., None]).reshape(B, 1, H * HD)

    out_oracle = kvc.attention_decode(SPEC, jnp.asarray(q), cache, pos,
                                      backend="oracle")
    np.testing.assert_allclose(
        np.asarray(out_kernel), np.asarray(out_oracle), atol=2e-2, rtol=2e-2
    )


def test_compressed_cache_smaller():
    # production page size (the tiny test SPEC above trades ratio for speed):
    # the cache holds a 2048-word page in 3588 B of int32 leaves (256
    # pointer + 512 delta lanes, 64 outlier values, 64 indices, 1 count)
    # against 4096 B raw, and the counts are those allocated bytes
    spec = kvc.KVSpec(n_kv=8, head_dim=128, max_len=32768)
    per_stream = 16384 * 3588 + 2 * 1024 * 2 + 4      # pages, tail ring, counter
    assert spec.compressed_bytes(64) == 2 * 64 * per_stream
    assert abs(spec.compressed_bytes(64) / spec.raw_bytes(64) - 3588 / 4096) < 1e-3, (
        spec.compressed_bytes(64), spec.raw_bytes(64))
    # the opt-in resident region is honest accounting: it adds the decoded
    # copy (>= raw size) on top of the compressed pages
    import dataclasses
    res = dataclasses.replace(spec, resident_decode=True)
    assert res.compressed_bytes(64) >= spec.compressed_bytes(64) + spec.raw_bytes(64) \
        - 2 * 64 * spec.group_tokens * spec.row_words * spec.word_bytes


# ---------------------------------------------------------------------------
# incremental resident decode (spec.resident_decode)
# ---------------------------------------------------------------------------

def _bit_equal(a, b, msg):
    np.testing.assert_array_equal(np.asarray(a).view(np.uint16),
                                  np.asarray(b).view(np.uint16), err_msg=msg)


def test_resident_decode_bit_identical_over_random_schedule():
    """Property test for the incremental decoded-page region: drive a
    random admit(bulk-prefill)/append/flush schedule and assert, after
    every burst, that ``k_dec``/``v_dec`` are bit-identical to a
    from-scratch ``_decompress_all`` of the page slots, and that
    ``read_full`` on the resident cache is bit-identical to the
    non-resident cache fed the same tokens."""
    import dataclasses

    from repro.serving.engine import KVSession

    fr = FRConfig(word_bits=16, page_words=128, width_set=(4, 8),
                  bucket_caps=(32, 128), num_bases=14, outlier_cap=16)
    spec = kvc.KVSpec(n_kv=2, head_dim=16, max_len=32, fr=fr,
                      resident_decode=True)
    spec0 = dataclasses.replace(spec, resident_decode=False)
    assert spec.group_tokens == 4          # flushes mid-schedule, not per-token
    rng = np.random.default_rng(7)

    def mk(n):
        ch = rng.normal(0, 1, (1, 1, 2, 16)) * 2
        return jnp.asarray(
            (ch + rng.normal(0, 0.1, (B, n, 2, 16))).astype(np.float32))

    sample = mk(32)
    w = jax.lax.bitcast_convert_type(sample.astype(jnp.bfloat16), jnp.uint16)
    table = fit_fr_bases(w.astype(jnp.int32).reshape(-1), fr)

    sess = KVSession(spec, B, table)                 # auto -> resident reads
    plain = kvc.init_compressed(spec0, B, table)
    _bit_equal(sess.cache["k_dec"],
               kvc._decompress_all(spec, sess.cache["k_pages"], table),
               "init region != from-scratch decode of zero pages")
    import functools
    append0 = jax.jit(functools.partial(kvc.append, spec0))

    pos = 0
    while pos < spec.max_len - 6:
        burst = int(rng.integers(1, 6))
        ks, vs = mk(burst), mk(burst)
        if burst > 1 and rng.random() < 0.5:
            sess.prefill(ks, vs)                     # admit: bulk fori_loop
        else:
            for t in range(burst):                   # decode-loop appends
                sess.append(ks[:, t:t + 1], vs[:, t:t + 1])
        for t in range(burst):
            plain = append0(plain, ks[:, t:t + 1], vs[:, t:t + 1],
                            jnp.int32(pos + t))
        pos += burst
        for side in ("k", "v"):
            _bit_equal(sess.cache[f"{side}_dec"],
                       kvc._decompress_all(spec, sess.cache[f"{side}_pages"],
                                           table),
                       f"{side}_dec diverged from from-scratch @ pos {pos}")
        K1, V1, val1 = kvc.read_full(spec, sess.cache, jnp.int32(pos - 1))
        K0, V0, val0 = kvc.read_full(spec0, plain, jnp.int32(pos - 1))
        _bit_equal(K1, K0, f"read_full K @ pos {pos}")
        _bit_equal(V1, V0, f"read_full V @ pos {pos}")
        np.testing.assert_array_equal(np.asarray(val1), np.asarray(val0))

    q = jnp.asarray(rng.normal(0, 1, (B, 1, 4, 16)).astype(np.float32))
    out_res = kvc.attention_decode(spec, q, sess.cache, jnp.int32(pos - 1),
                                   backend="resident")
    out_auto = kvc.attention_decode(spec, q, sess.cache, jnp.int32(pos - 1),
                                    backend="auto")
    out_orc = kvc.attention_decode(spec0, q, plain, jnp.int32(pos - 1),
                                   backend="oracle")
    _bit_equal(out_res, out_orc, "resident attention != oracle")
    _bit_equal(out_auto, out_res, "auto did not pick the resident region")
    import pytest
    with pytest.raises(ValueError, match="resident_decode"):
        kvc.attention_decode(spec0, q, plain, jnp.int32(pos - 1),
                             backend="resident")


def test_kvsession_step_matches_manual_path():
    """KVSession.step (append + attend, one jitted dispatch each) equals
    the manual append/attention_decode sequence bit-for-bit."""
    from repro.serving.engine import KVSession

    rng = np.random.default_rng(11)
    n = 8
    ks, vs = _mk_kv(rng, n), _mk_kv(rng, n)
    table = _bases(np.concatenate([ks, vs], axis=1))
    spec = SPEC
    sess = KVSession(spec, B, table, backend="oracle")
    cache = kvc.init_compressed(spec, B, table)
    H = 8
    q = jnp.asarray(rng.normal(0, 1, (B, 1, H, HD)).astype(np.float32))
    for t in range(n):
        k, v = jnp.asarray(ks[:, t:t + 1]), jnp.asarray(vs[:, t:t + 1])
        got = sess.step(q, k, v)
        cache = kvc.append(spec, cache, k, v, jnp.int32(t))
        want = kvc.attention_decode(spec, q, cache, jnp.int32(t),
                                    backend="oracle")
        _bit_equal(got, want, f"session step @ {t}")
    assert sess.pos == n
