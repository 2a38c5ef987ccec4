"""Compiled batched XLA backend: three-way blob parity (xla / oracle /
Pallas-interpret) across width-set configs incl. forced spill, the same
parity inside jit with a traced table, batch-vs-loop
equivalence, memoized table upload, 'auto' backend resolution, paged
attention, and the throughput harness."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.format import BaseTable
from repro.core.gbdi_fr import FRConfig, fit_fr_bases, fr_decode, fr_encode
from repro.kernels import ops, xla


def _pages(cfg: FRConfig, n_pages: int, seed: int) -> jax.Array:
    rng = np.random.default_rng(seed)
    mask = (1 << cfg.word_bits) - 1
    centers = rng.integers(0, mask, cfg.num_bases)
    w = (centers[rng.integers(0, cfg.num_bases, (n_pages, cfg.page_words))]
         + rng.integers(-120, 120, (n_pages, cfg.page_words)))
    w[:, ::7] = 0
    return jnp.asarray((w & mask).astype(np.int64), dtype=jnp.int32)


PARITY_CFGS = [
    FRConfig(word_bits=16, page_words=256, num_bases=6, width_set=(4, 8),
             bucket_caps=(64, 192), outlier_cap=16),
    FRConfig(word_bits=16, page_words=256, num_bases=6, width_set=(2, 4, 8),
             bucket_caps=(16, 64, 160), outlier_cap=16),
    FRConfig(word_bits=32, page_words=256, num_bases=5, width_set=(8, 16),
             bucket_caps=(64, 192), outlier_cap=32),
    # spill-heavy corner: tiny buckets force the narrow->wide->outlier chain
    FRConfig(word_bits=16, page_words=128, num_bases=6, width_set=(2, 4, 8),
             bucket_caps=(16, 8, 8), outlier_cap=4),
    # v1-compat single width, full-page bucket (the KV/GRAD shape)
    FRConfig(word_bits=16, page_words=128, num_bases=4, delta_bits=8,
             outlier_cap=8),
    # adaptive bucket-cap profiles, incl. a forced-spill profile (8, 8)
    FRConfig(word_bits=16, page_words=256, num_bases=6, width_set=(4, 8),
             cap_profiles=((64, 192), (192, 64), (8, 8)), outlier_cap=16),
    FRConfig(word_bits=32, page_words=256, num_bases=5, width_set=(8, 16),
             cap_profiles=((64, 192), (128, 32)), outlier_cap=32),
]


def _cfg_id(c):
    return (f"wb{c.word_bits}_w{'-'.join(map(str, c.width_set))}"
            f"_caps{'-'.join(map(str, c.bucket_caps))}"
            + (f"_p{c.num_profiles}" if c.num_profiles > 1 else ""))


@pytest.mark.parametrize("cfg", PARITY_CFGS, ids=_cfg_id)
def test_three_way_blob_parity(cfg):
    """xla, oracle, and interpret-mode Pallas blobs/decodes are all
    bit-identical, including under bucket spill and outlier drop."""
    x = _pages(cfg, 4, cfg.page_words + cfg.num_bases)
    table = fit_fr_bases(x, cfg)
    rb = fr_encode(x, table, cfg)
    xb = ops.encode_pages(x, table, cfg, backend="xla")
    kb = ops.encode_pages(x, table, cfg, backend="kernel")
    assert set(rb) == set(xb) == set(kb)
    for k in rb:
        np.testing.assert_array_equal(np.asarray(xb[k]), np.asarray(rb[k]),
                                      err_msg=f"xla vs oracle: {k}")
        np.testing.assert_array_equal(np.asarray(kb[k]), np.asarray(rb[k]),
                                      err_msg=f"kernel vs oracle: {k}")
    ref_dec = np.asarray(fr_decode(rb, table, cfg))
    np.testing.assert_array_equal(
        np.asarray(ops.decode_pages(xb, table, cfg, backend="xla")), ref_dec)
    np.testing.assert_array_equal(
        np.asarray(ops.decode_pages(kb, table, cfg, backend="kernel")), ref_dec)


@pytest.mark.parametrize("direction", ["encode", "decode"])
@pytest.mark.parametrize("cfg", PARITY_CFGS, ids=_cfg_id)
def test_traced_table_parity(cfg, direction):
    """Inside jit with the table a jit argument, as the KV flush and the
    gradient exchange call it, the XLA chain's blobs and words are
    bit-identical to the oracle's."""
    x = _pages(cfg, 4, cfg.page_words + cfg.num_bases)
    table = fit_fr_bases(x, cfg)
    rb = fr_encode(x, table, cfg)
    if direction == "encode":
        xb = jax.jit(lambda xs, t: ops.encode_pages(xs, t, cfg, backend="xla"))(x, table)
        assert set(xb) == set(rb)
        for k in rb:
            np.testing.assert_array_equal(np.asarray(xb[k]), np.asarray(rb[k]),
                                          err_msg=f"traced xla vs oracle: {k}")
    else:
        words = jax.jit(lambda b, t: ops.decode_pages(b, t, cfg, backend="xla"))(rb, table)
        np.testing.assert_array_equal(np.asarray(words),
                                      np.asarray(fr_decode(rb, table, cfg)))


def test_forced_spill_parity_and_counters():
    """A narrow bucket overflowing into a same-value wide base must spill
    (not drop) identically on both compiled paths."""
    cfg = FRConfig(word_bits=16, page_words=256, num_bases=4, width_set=(4, 8),
                   bucket_caps=(8, 240), outlier_cap=8)
    table = BaseTable(jnp.asarray([1000, 1000, -5000, 20000], jnp.int32),
                      jnp.asarray([4, 8, 8, 4], jnp.int32))
    rng = np.random.default_rng(1)
    x = jnp.asarray((1000 + rng.integers(-7, 8, (3, 256))).astype(np.int32))
    rb, xb = fr_encode(x, table, cfg), xla.encode_pages(x, table, cfg)
    for k in rb:
        np.testing.assert_array_equal(np.asarray(xb[k]), np.asarray(rb[k]), err_msg=k)
    assert int(np.asarray(xb["n_spilled"]).sum()) > 0
    assert int(np.asarray(xb["n_dropped"]).sum()) == 0
    np.testing.assert_array_equal(np.asarray(xla.decode_pages(xb, table, cfg)),
                                  np.asarray(x))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_property_batch_equals_page_loop(seed):
    """One batched dispatch over N pages == N single-page dispatches: the
    leading batch axis must never couple pages."""
    cfg = FRConfig(word_bits=16, page_words=128, num_bases=5,
                   width_set=(4, 8), bucket_caps=(32, 96), outlier_cap=8)
    x = _pages(cfg, 5, seed)
    table = fit_fr_bases(x, cfg)
    batched = xla.encode_pages(x, table, cfg)
    for p in range(x.shape[0]):
        single = xla.encode_pages(x[p:p + 1], table, cfg)
        for k in batched:
            np.testing.assert_array_equal(
                np.asarray(batched[k][p:p + 1]), np.asarray(single[k]),
                err_msg=f"page {p}: {k}")
        np.testing.assert_array_equal(
            np.asarray(xla.decode_pages(batched, table, cfg))[p],
            np.asarray(xla.decode_pages(single, table, cfg))[0])


def test_leading_batch_axes_roundtrip():
    """(B, n_pages, P) shaped inputs keep their leading axes through
    encode/decode (the kv-cache layout) and match the flat encoding."""
    cfg = FRConfig(word_bits=16, page_words=128, num_bases=4,
                   width_set=(4, 8), bucket_caps=(32, 96), outlier_cap=8)
    x = _pages(cfg, 6, 7).reshape(2, 3, cfg.page_words)
    table = fit_fr_bases(x, cfg)
    blob = xla.encode_pages(x, table, cfg)
    assert blob["ptrs"].shape[:2] == (2, 3) and blob["n_out"].shape == (2, 3)
    flat = xla.encode_pages(x.reshape(6, cfg.page_words), table, cfg)
    for k in blob:
        np.testing.assert_array_equal(
            np.asarray(blob[k]).reshape(np.asarray(flat[k]).shape),
            np.asarray(flat[k]), err_msg=k)
    dec = xla.decode_pages(blob, table, cfg)
    assert dec.shape == x.shape
    np.testing.assert_array_equal(
        np.asarray(dec).reshape(6, -1),
        np.asarray(xla.decode_pages(flat, table, cfg)))


def test_table_prep_memoized():
    """Repeated encode_pages with the same fitted table must not re-upload
    or rebuild device constants — the second call is a cache hit."""
    cfg = FRConfig(word_bits=16, page_words=128, num_bases=4,
                   width_set=(4, 8), bucket_caps=(32, 96), outlier_cap=8)
    x = _pages(cfg, 2, 11)
    table = fit_fr_bases(x, cfg)
    xla.table_cache_clear()
    xla.encode_pages(x, table, cfg)
    after_first = xla.table_cache_info()
    assert after_first["misses"] == 1 and after_first["size"] == 1
    xla.encode_pages(x, table, cfg)
    xla.decode_pages(xla.encode_pages(x, table, cfg), table, cfg)
    info = xla.table_cache_info()
    assert info["misses"] == 1, info          # no rebuilds
    assert info["hits"] >= 3, info            # every later call hit
    # the prepared constants are the very same device buffers
    assert xla.prepare_table(table, cfg) is xla.prepare_table(table, cfg)
    # a different table is a different entry, not a collision
    table2 = BaseTable(table.bases + 1, table.widths)
    xla.encode_pages(x, table2, cfg)
    assert xla.table_cache_info()["misses"] == 2
    # content-keyed: an equal-content table hits regardless of identity
    table3 = BaseTable(jnp.asarray(np.asarray(table.bases)), table.widths)
    assert xla.prepare_table(table3, cfg) is xla.prepare_table(table, cfg)
    assert xla.table_cache_info()["misses"] == 2


def test_table_prep_survives_a_donated_table():
    """The memo holds its own copies of a table's buffers: a caller that
    donates its table after an eager call (KVSession donates its tables
    with the cache) leaves later calls with an equal table working."""
    cfg = FRConfig(word_bits=16, page_words=128, num_bases=4,
                   width_set=(4, 8), bucket_caps=(32, 96), outlier_cap=8)
    x = _pages(cfg, 2, 13)
    table = fit_fr_bases(x, cfg)
    xla.table_cache_clear()
    mine = jax.tree.map(jnp.copy, table)
    blob = xla.encode_pages(x, mine, cfg)
    jax.jit(lambda t: jax.tree.map(lambda a: a + 1, t), donate_argnums=0)(mine)
    assert mine.bases.is_deleted()
    again = xla.encode_pages(x, table, cfg)
    assert xla.table_cache_info()["hits"] == 1
    for k in blob:
        np.testing.assert_array_equal(np.asarray(again[k]), np.asarray(blob[k]), err_msg=k)
    np.testing.assert_array_equal(np.asarray(xla.decode_pages(again, table, cfg)),
                                  np.asarray(fr_decode(blob, table, cfg)))


def test_table_prep_never_serves_stale_constants_after_gc():
    """Invariant lock: the memo used to key on id(leaf), which was safe
    only because every keyed table was pinned alive by its cache entry —
    one refactor away from CPython recycling a freed address and serving
    stale device constants for different data.  Build and drop tables in a
    tight loop — every prepare must reflect the table it was handed, and
    distinct contents must never alias to a cache hit."""
    import gc

    cfg = FRConfig(word_bits=16, page_words=128, num_bases=4,
                   width_set=(4, 8), bucket_caps=(32, 96), outlier_cap=8)
    xla.table_cache_clear()
    for i in range(12):
        bases = np.asarray([100, 900, 5000, 20000], np.int32) + 7 * i
        table = BaseTable(jnp.asarray(bases),
                          jnp.asarray([4, 8, 4, 8], jnp.int32))
        prep = xla.prepare_table(table, cfg)
        np.testing.assert_array_equal(np.asarray(prep.bases), bases)
        np.testing.assert_array_equal(np.asarray(prep.cls),
                                      np.asarray([0, 1, 0, 1], np.int32))
        del table, prep
        gc.collect()      # free the leaves so their addresses can recycle
    info = xla.table_cache_info()
    assert info["misses"] == 12 and info["hits"] == 0, info


def test_table_prep_cache_bounded_lru():
    """Regression: the digest-keyed table memo is LRU-bounded — preparing
    more distinct tables than the cap keeps the cache at the cap, and an
    evicted table rebuilds correctly on re-prepare (a fresh miss with the
    right constants, never stale ones), while recent entries still hit."""
    cfg = FRConfig(word_bits=16, page_words=128, num_bases=4,
                   width_set=(4, 8), bucket_caps=(32, 96), outlier_cap=8)
    xla.table_cache_clear()
    n = xla._PREP_CAP + 8
    tables = []
    for i in range(n):
        bases = np.asarray([100, 900, 5000, 20000], np.int32) + 3 * i
        table = BaseTable(jnp.asarray(bases),
                          jnp.asarray([4, 8, 4, 8], jnp.int32))
        tables.append((table, bases))
        xla.prepare_table(table, cfg)
        assert xla.table_cache_info()["size"] <= xla._PREP_CAP
    info = xla.table_cache_info()
    assert info["size"] == xla._PREP_CAP and info["misses"] == n, info
    # oldest entry was evicted: re-preparing is a miss, not stale constants
    t0, b0 = tables[0]
    prep0 = xla.prepare_table(t0, cfg)
    np.testing.assert_array_equal(np.asarray(prep0.bases), b0)
    assert xla.table_cache_info()["misses"] == n + 1
    # most recent entry is still resident
    tn, bn = tables[-1]
    hits = xla.table_cache_info()["hits"]
    np.testing.assert_array_equal(
        np.asarray(xla.prepare_table(tn, cfg).bases), bn)
    assert xla.table_cache_info()["hits"] == hits + 1


def test_auto_backend_resolves_compiled():
    """'auto' never resolves to interpret mode: off-TPU it must be the
    compiled xla path (and the default everywhere in ops)."""
    assert jax.default_backend() != "tpu"     # CI/container precondition
    assert ops.resolve_backend("auto") == "xla"
    assert ops.resolve_backend(None) == "xla"
    assert ops.resolve_backend("kernel") == "kernel"   # explicit request only
    with pytest.raises(ValueError):
        ops.resolve_backend("vulkan")
    cfg = FRConfig(word_bits=16, page_words=128, num_bases=4,
                   width_set=(4, 8), bucket_caps=(32, 96), outlier_cap=8)
    x = _pages(cfg, 2, 13)
    table = fit_fr_bases(x, cfg)
    auto_blob = ops.encode_pages(x, table, cfg)        # default backend
    ref_blob = fr_encode(x, table, cfg)
    for k in ref_blob:
        np.testing.assert_array_equal(np.asarray(auto_blob[k]),
                                      np.asarray(ref_blob[k]), err_msg=k)


def test_paged_attention_xla_matches_oracle():
    """Compiled paged-attention over compressed pages + tail merge equals
    the explicit decompress-then-attend oracle."""
    from repro.kernels.gbdi_paged_attn import merge_softmax
    from repro.serving import kv_cache as kvc

    KV, HD, B, n = 4, 32, 2, 24
    spec = kvc.KVSpec(n_kv=KV, head_dim=HD, max_len=64,
                      fr=FRConfig(word_bits=16, page_words=128, width_set=(4, 8),
                                  bucket_caps=(32, 128), num_bases=14,
                                  outlier_cap=16))
    rng = np.random.default_rng(3)
    ch = rng.normal(0, 1, (1, 1, KV, HD)) * 2
    ks = (ch + rng.normal(0, 0.1, (B, n, KV, HD))).astype(np.float32)
    vs = (ch + rng.normal(0, 0.1, (B, n, KV, HD))).astype(np.float32)
    w = jax.lax.bitcast_convert_type(
        jnp.asarray(np.concatenate([ks, vs], 1)).astype(jnp.bfloat16), jnp.uint16)
    table = fit_fr_bases(w.astype(jnp.int32).reshape(-1), spec.fr)
    cache = kvc.init_compressed(spec, B, table)
    for t in range(n):
        cache = kvc.append(spec, cache, jnp.asarray(ks[:, t:t+1]),
                           jnp.asarray(vs[:, t:t+1]), jnp.int32(t))
    H = 8
    G = H // KV
    pos = jnp.int32(n - 1)
    q = rng.normal(0, 1, (B, 1, H, HD)).astype(np.float32)
    qg = jnp.asarray(q).reshape(B, KV, G, HD)

    acc, m, l = xla.paged_attention_decode(
        qg, cache["k_pages"], cache["v_pages"], cache["table"], pos, spec.fr,
        n_kv=KV, hd=HD, groups=G,
    )
    pt = spec.group_tokens
    lim = (int(pos) // pt) * pt
    Kt = cache["k_tail"].astype(jnp.float32)
    Vt = cache["v_tail"].astype(jnp.float32)
    tail_valid = (lim + jnp.arange(pt)) <= pos
    lg = jnp.einsum("bkgh,btkh->bkgt", qg, Kt) / np.sqrt(HD)
    lg = jnp.where(tail_valid[None, None, None, :], lg, -1e30)
    m2 = lg.max(-1)
    p2 = jnp.exp(lg - m2[..., None])
    accm, mm, lm = merge_softmax(acc, m, l,
                                 jnp.einsum("bkgt,btkh->bkgh", p2, Vt),
                                 m2, p2.sum(-1))
    out_xla = (accm / lm[..., None]).reshape(B, 1, H * HD)
    out_oracle = kvc.attention_decode(spec, jnp.asarray(q), cache, pos,
                                      backend="oracle")
    np.testing.assert_allclose(np.asarray(out_xla), np.asarray(out_oracle),
                               atol=2e-2, rtol=2e-2)
    # the wired-in serving path (backend='auto') is the same computation
    out_auto = kvc.attention_decode(spec, jnp.asarray(q), cache, pos)
    np.testing.assert_allclose(np.asarray(out_auto, np.float32),
                               np.asarray(out_xla), atol=2e-2, rtol=2e-2)


def test_throughput_harness_smoke(tmp_path):
    """measure_throughput rows are warmed/median and the artifact parses."""
    import json

    from repro.eval.codecs import default_codecs
    from repro.eval.run import (
        format_throughput_table, measure_throughput, throughput_artifact,
        throughput_summary,
    )
    from repro.eval.workloads import default_workloads

    wl = default_workloads().get("ml_kvcache_bf16")
    data = wl.generate(1 << 16, 0)
    rows = [measure_throughput(wl, default_codecs().make(c, wl.word_bits),
                               data, repeats=2) for c in ("fr", "fr_xla")]
    for r in rows:
        assert r["enc_gib_s"] > 0 and r["dec_gib_s"] > 0 and r["repeats"] == 2
    summ = throughput_summary(rows)
    assert {s["codec"] for s in summ} == {"fr", "fr_xla"}
    assert "fr_xla" in format_throughput_table(rows)
    art = throughput_artifact(rows, codecs="fr,fr_xla", n_bytes=1 << 16,
                              kernel_n_bytes=1 << 16, repeats=2, seed=0)
    out = tmp_path / "BENCH_throughput.json"
    out.write_text(json.dumps(art))
    back = json.loads(out.read_text())
    assert back["bench"] == "throughput" and len(back["rows"]) == 2
    assert back["auto_backend"] == "xla"
    assert {"workload", "codec", "enc_gib_s", "dec_gib_s"} <= set(back["rows"][0])


def test_paged_attention_rejects_rows_wider_than_a_page():
    """A 32x128-word row (deepseek-7b) spans two 2048-word pages: the
    paged path must refuse it, not attend over zero tokens per page."""
    from repro.serving.kv_cache import KV_FR

    n_kv, hd, B, slots = 32, 128, 1, 2
    pages = {"ptrs": jnp.zeros((B, slots, KV_FR.ptr_lanes), jnp.int32),
             "deltas": jnp.zeros((B, slots, KV_FR.delta_lanes), jnp.int32),
             "out_vals": jnp.zeros((B, slots, KV_FR.outlier_cap), jnp.int32),
             "out_idx": jnp.zeros((B, slots, KV_FR.outlier_cap), jnp.int32),
             "n_out": jnp.zeros((B, slots), jnp.int32)}
    table = jnp.zeros((KV_FR.num_bases,), jnp.int32)
    q = jnp.zeros((B, n_kv, 1, hd), jnp.float32)
    with pytest.raises(ValueError, match="does not tile"):
        xla.paged_attention_decode(q, pages, pages, table, jnp.int32(0), KV_FR,
                                   n_kv=n_kv, hd=hd, groups=1)
