"""The comparisons that decide ``correct``, and the controls.

Every number compared is a count or a gap of the program's output against
the plain reference (``refcodec`` for pages, a float32 attention for the
decode step), and has a limit of its own in ``limits/<cell>.json``.

The control is that reference put in the program's place and computed one
precision below the configuration's: float8 (e4m3) for bf16 words and
activations, int16 for 32-bit integer words.  It has to fail.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import gen
import refcodec

KV_PAGE_FIELDS = ("ptrs", "deltas", "out_vals", "out_idx", "n_out")
BLOCK_PAGES = 1024


def _blocks(fn, x: np.ndarray | jax.Array, *args):
    """fn over row blocks of x; outputs concatenated on the host."""
    outs = [jax.tree.map(np.asarray, fn(jnp.asarray(x[i:i + BLOCK_PAGES]), *args))
            for i in range(0, x.shape[0], BLOCK_PAGES)]
    return jax.tree.map(lambda *a: np.concatenate(a), *outs)


def pages_off(prog: dict, ref: dict, fields, n: int) -> np.ndarray:
    """(n,) True where any field of a page differs (or is missing)."""
    off = np.zeros(n, bool)
    for k in fields:
        p, r = prog.get(k), ref[k]
        if p is None or p.shape != r.shape:
            return np.ones(n, bool)
        off |= (p != r).reshape(n, -1).any(axis=1)
    return off


def words_off(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """(rows,) count of differing words per row (every word on a shape mismatch)."""
    if prog.shape != ref.shape:
        return np.full(ref.shape[0], ref[0].size)
    return (prog != ref).reshape(ref.shape[0], -1).sum(axis=1)


def lower_words(x: np.ndarray, word_bits: int) -> np.ndarray:
    """Words one precision below: bf16 -> float8 e4m3 -> bf16, int32 -> int16."""
    if word_bits == 16:
        v = gen.bf16_of(jnp.asarray(x)).astype(jnp.float8_e4m3fn)
        return np.asarray(gen.words16(v.astype(jnp.bfloat16)))
    return x.astype(np.int16).astype(np.int32)


def verdict(numbers: dict, limits: dict) -> dict:
    missing = set(numbers) - set(limits)
    if missing:
        raise KeyError(f"no limit for {sorted(missing)}")
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}


# ---------------------------------------------------------------------------
# roundtrip cells: sampled pages of the last round
# ---------------------------------------------------------------------------

def roundtrip(out: dict, bases, widths, f, limits: dict) -> tuple[dict, int]:
    x = out["x"]
    n = x.shape[0]
    ref_blob = _blocks(refcodec.encode, x, bases, widths, f)
    ref_dec = _blocks(refcodec.roundtrip, x, bases, widths, f)
    off = pages_off(out["blob"], ref_blob, refcodec.BLOB_FIELDS, n)
    wrong = words_off(out["dec"], ref_dec)
    numbers = {"blob_pages_off": int(off.sum()), "words_off": int(wrong.sum())}
    return verdict(numbers, limits), int((off | (wrong > 0)).sum())


def roundtrip_control(out: dict, bases, widths, f) -> dict:
    low = lower_words(out["x"], f.word_bits)
    return {"x": out["x"], "blob": _blocks(refcodec.encode, low, bases, widths, f),
            "dec": _blocks(refcodec.roundtrip, low, bases, widths, f)}


# ---------------------------------------------------------------------------
# decode cell: the cache's flushed pages and decoded region, and attention
# ---------------------------------------------------------------------------

def _raw_rows(drv, rows, name: str) -> np.ndarray:
    return np.stack([np.asarray(gen.words16(drv.raw_row(b, t, name))).reshape(-1)
                     for b, t in rows])


def _all_rows(drv, name: str) -> jax.Array:
    """(B, T0 + S, n_kv, hd) raw bf16 rows of every position."""
    steps = jnp.swapaxes(drv.inp[name][:, :, 0], 0, 1)
    return jnp.concatenate([drv.inp[f"{name}_ctx"], steps], axis=1)


def _through_reference(drv, x: jax.Array) -> jax.Array:
    """bf16 rows -> what the format's reference gives back for them."""
    w = np.asarray(gen.words16(x)).reshape(-1, drv.f.page_words)
    back = _blocks(refcodec.roundtrip, w, drv.bases, drv.widths, drv.f)
    return gen.bf16_of(jnp.asarray(back)).reshape(x.shape)


@jax.jit
def attention(q: jax.Array, K: jax.Array, V: jax.Array, pos: jax.Array) -> jax.Array:
    """Float32 attention of one decode step over positions [0, pos].
    q (B, 1, H, hd); K, V (B, L, Kv, hd) -> (B, H*hd)."""
    B, _, H, hd = q.shape
    G = H // K.shape[2]
    hp = jax.lax.Precision.HIGHEST
    qf = q[:, 0].astype(jnp.float32)
    Kf = jnp.repeat(K.astype(jnp.float32), G, axis=2)
    Vf = jnp.repeat(V.astype(jnp.float32), G, axis=2)
    s = jnp.einsum("bhd,blhd->bhl", qf, Kf, precision=hp) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.arange(K.shape[1]) <= pos, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhl,blhd->bhd", p, Vf, precision=hp).reshape(B, H * hd)


def _gaps(o: np.ndarray, ref: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per answer (one sequence's output at one step): the widest gap to
    the reference as a share of the reference's largest magnitude, and the
    share (%) of elements more than one bf16 ulp of the reference off."""
    o = o.reshape(ref.shape)
    err = np.abs(o - ref)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(ref), 2.0**-126))) - 7)
    return err.max(axis=1) / np.abs(ref).max(), 100.0 * (err > ulp).mean(axis=1)


def decode(drv, out: dict, limits: dict) -> tuple[dict, int]:
    f, rows, ppr = drv.f, out["rows"], drv.ppr
    n = len(rows)
    numbers, failed = {"kv_pages_off": 0, "kv_words_off": 0}, 0
    for name in ("k", "v"):
        raw = _raw_rows(drv, rows, name).reshape(n * ppr, f.page_words)
        ref_pages = _blocks(refcodec.encode, raw, drv.bases, drv.widths, f)
        ref_words = _blocks(refcodec.roundtrip, raw, drv.bases, drv.widths, f)
        prog = {k: v.reshape(n * ppr, *v.shape[2:]) for k, v in out[f"{name}_pages"].items()}
        off = pages_off(prog, ref_pages, KV_PAGE_FIELDS, n * ppr)
        wrong = words_off(out[f"{name}_words"], ref_words.reshape(n, -1))
        numbers["kv_pages_off"] += int(off.sum())
        numbers["kv_words_off"] += int(wrong.sum())
        failed += int((off.reshape(n, ppr).any(axis=1) | (wrong > 0)).sum())
    K = _through_reference(drv, _all_rows(drv, "k"))
    V = _through_reference(drv, _all_rows(drv, "v"))
    T0, gaps, offs = drv.mix["context"], [], []
    for i, outs in out["attn"].items():
        ref = np.asarray(attention(drv.inp["q"][i], K, V, jnp.int32(T0 + i)))
        for o in outs:
            gap, off = _gaps(o, ref)
            gaps.append(gap.max())
            offs.append(off.max())
            failed += int(((gap > limits["attn_gap"]) | (off > limits["attn_off_pct"])).sum())
    if not gaps:
        raise RuntimeError("the window finished no sampled step")
    numbers["attn_gap"] = float(max(gaps))
    numbers["attn_off_pct"] = float(max(offs))
    return verdict(numbers, limits), failed


def decode_control(drv, out: dict) -> dict:
    f, rows, ppr = drv.f, out["rows"], drv.ppr
    n = len(rows)
    ctl = {"rows": rows, "last": out["last"], "attn": {}}
    for name in ("k", "v"):
        low = lower_words(_raw_rows(drv, rows, name), 16).reshape(n * ppr, f.page_words)
        pages = _blocks(refcodec.encode, low, drv.bases, drv.widths, f)
        ctl[f"{name}_pages"] = {k: pages[k].reshape(n, ppr, *pages[k].shape[1:])
                                for k in KV_PAGE_FIELDS}
        ctl[f"{name}_words"] = _blocks(refcodec.roundtrip, low, drv.bases, drv.widths,
                                       f).reshape(n, -1)
    fp8 = jnp.float8_e4m3fn
    K = _through_reference(drv, _all_rows(drv, "k")).astype(fp8)
    V = _through_reference(drv, _all_rows(drv, "v")).astype(fp8)
    for i in out["attn"]:
        q = drv.inp["q"][i].astype(fp8)
        ctl["attn"][i] = [np.asarray(attention(q, K, V, jnp.int32(drv.mix["context"] + i)))]
    return ctl
