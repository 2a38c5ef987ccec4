"""Traffic kind ``mla_decode``: the program's ``serving.engine.KVSession``
over a stack of multi-head latent attention layers (``models/mla.py``),
each with its own compressed latent cache (``serving.kv_cache.LatentSpec``).

Set-up makes the layers' weights and base tables from the configuration,
then every layer's context: hidden states made on the chip in chunks,
projected to latent rows by the program (``mla.latent_rows``) and
bulk-prefilled into the caches as whole flush groups.  The window runs
closed-loop ``step`` calls, each given every layer's hidden state and
blocked until the layers' outputs are ready; after ``answer`` steps the
batch restarts from the post-prefill caches.

Checked, every time: the flushed pages of sampled context groups (the
driver holds their raw latent rows) against the reference codec; the
resident rows of those groups and of each sequence's last flushed group
against the reference codec's decode of the program's pages, and those
and the raw rows of the current group against the reference's own latent
rows; and every kept layer output of the sampled steps, and its latent
attention output (the heads' softmax-weighted latent rows, before
``W_UV``), against the float32 reference (``refmla``) over the reference
codec's round trip of the reference's latent rows.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import byname
import cells
import checks
import gen
import refcodec
import refmla
import table
from fmt import Format

span = jax.profiler.TraceAnnotation
BLOCK = 1024          # positions per block of the reference's attention
PAGE_BLOCK = 1024     # pages per block of the reference codec


def mla_flops(d: refmla.Dims, layers: int, batch: int, positions: int, steps: int) -> int:
    """FLOPs the absorbed decode needs over ``steps`` steps that attend
    ``positions`` valid positions in all, for ``layers`` layers: every head
    scores the whole latent row and reads its latent part as the value, and
    the projections (q_a, q_b, kv_a, kv_b's key and value halves, o_proj)
    take a multiply and an add per weight per sequence.  Masked positions
    are not counted."""
    params = (d.hidden * d.q_lora + d.q_lora * d.heads * (d.nope + d.rope)
              + d.hidden * (d.latent + d.rope) + d.latent * d.heads * (d.nope + d.v)
              + d.heads * d.v * d.hidden)
    attn = 2 * batch * d.heads * positions * (2 * d.latent + d.rope)
    return layers * (attn + 2 * batch * steps * params)


def _words(x: jax.Array) -> np.ndarray:
    return np.asarray(gen.words16(x))


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        self.config, self.mix, self.seed = config, mix, seed
        self.f = Format.from_config(config)
        self.d = refmla.Dims.of(config)
        self.L = config["num_hidden_layers"]
        self.R = self.d.latent + self.d.rope

    def setup(self) -> None:
        from repro.models import mla
        from repro.serving.engine import KVSession
        from repro.serving.kv_cache import LatentSpec

        fam = byname.load("values", self.config["values"]["family"])
        mix, B, L = self.mix, self.mix["batch"], self.L
        cfg = mla.MLAConfig.from_hf(self.config)
        cal = self.config["values"]["calibration_seed"]
        self.params = [mla.init(jax.random.fold_in(gen.key_of(cal, 2), i), cfg)
                       for i in range(L)]
        project = jax.jit(lambda ps, x, pos: jnp.stack(
            [mla.latent_rows(p, cfg, x[i], pos) for i, p in enumerate(ps)]))
        xc = fam.calibration(self.config)
        pos_c = jnp.linspace(0, mix["context"] - 1, xc.shape[2]).astype(jnp.int32)
        cal_rows = project(self.params, xc, pos_c)
        self.tables = [table.fit(_words(cal_rows[i]), self.f) for i in range(L)]
        spec = LatentSpec(latent_dim=self.d.latent, rope_dim=self.d.rope, max_len=mix["max_len"],
                          fr=cells.fr_config(self.f), resident_decode=True)
        self.G, self.gp = spec.group_tokens, spec.group_pages
        self.sess = KVSession(spec, B, [cells.base_table(*t) for t in self.tables],
                              layers=(cfg, self.params))
        T0, C = mix["context"], mix["prefill_chunk"]
        rng = gen.host_rng(self.seed, 4)
        n = mix["check_rows"]
        picks = zip(rng.integers(0, L, n), rng.integers(0, B, n), rng.integers(0, T0, n))
        self.ctx_groups = sorted({(int(i), int(b), int(t) // self.G) for i, b, t in picks})
        self.raw: dict[tuple[int, int, int], np.ndarray] = {}
        for c in range(T0 // C):
            rows = project(self.params, fam.context(self.config, self.seed, c, B, C),
                           c * C + jnp.arange(C))
            for i, b, g in self.ctx_groups:
                if c * C <= g * self.G < (c + 1) * C:
                    at = g * self.G - c * C
                    self.raw[i, b, g] = _words(rows[i, b, at:at + self.G]).reshape(-1)
            self.sess.prefill(rows)
        self.post = jax.block_until_ready(self.sess.cache)
        self.x = jax.block_until_ready(fam.steps(self.config, self.seed, B, mix["answer"]))
        jax.block_until_ready(self.sess.step(self.x[0]))       # compiles the step
        self.restart()
        S = mix["answer"]
        pick = gen.host_rng(self.seed, 3).choice(S - 1, min(S - 1, mix["check_steps"] - 1),
                                                 replace=False)
        self.sample_steps = sorted({*pick.tolist(), S - 1})      # the longest is always in
        self.kept: dict[int, list] = {i: [] for i in self.sample_steps}

    def restart(self) -> None:
        self.sess.cache = self.post
        self.sess.pos = self.mix["context"]

    def window(self, seconds: float) -> dict:
        S, T0, steps, positions, times = self.mix["answer"], self.mix["context"], 0, 0, []
        t = t0 = time.perf_counter()
        with span("bench.window"):
            done = False
            while not done:
                for i in range(S):
                    with span("bench.step"):
                        out = jax.block_until_ready(self.sess.step(self.x[i]))
                    steps += 1
                    positions += T0 + i + 1           # step i attends [0, T0 + i]
                    if i in self.kept:
                        self.kept[i].append(out)
                    now = time.perf_counter()
                    times.append(now - t)
                    t = now
                    if now - t0 >= seconds:
                        done = True
                        break
                else:
                    self.restart()
        elapsed = time.perf_counter() - t0
        B = self.mix["batch"]
        return {"elapsed": elapsed, "attempted": steps * B, "times": times,
                "metrics": {"decode_tokens_per_s": steps * B / elapsed},
                "work": {"steps": steps,
                         "mla_flops": mla_flops(self.d, self.L, B, positions, steps)}}

    def outputs(self) -> dict:
        last = self.sess.pos - 1                          # the last position written
        flushed = (last + 1) // self.G - 1                # the last group flushed
        B = self.mix["batch"]
        groups = sorted(set(self.ctx_groups)
                        | {(i, b, flushed) for i in range(self.L) for b in range(B)})
        out = {"groups": groups, "last": last, "pages": [], "words": []}
        for i, b, g in groups:
            c = self.sess.cache[i]
            out["pages"].append({k: np.asarray(v[b, g * self.gp:(g + 1) * self.gp])
                                 for k, v in c["c_pages"].items()})
            out["words"].append(_words(c["c_dec"][b, g * self.G:(g + 1) * self.G]).reshape(-1))
        n = last % self.G + 1                             # rows of the current group
        out["tail"] = [_words(c["c_tail"][:, :n]).reshape(B, -1) for c in self.sess.cache]
        out["dropped"] = [int(np.asarray(c["c_dropped"]).sum()) for c in self.sess.cache]
        print(f"latent cache: {sum(out['dropped'])} words dropped by the flushes "
              f"(per layer {out['dropped']})", file=sys.stderr, flush=True)
        out["outputs"] = {i: [np.asarray(y.astype(jnp.float32)) for y, _ in outs]
                          for i, outs in self.kept.items() if outs}
        out["attn"] = {i: [np.asarray(o.astype(jnp.float32)) for _, o in outs]
                       for i, outs in self.kept.items() if outs}
        del self.sess, self.post, self.kept
        return out

    def reference_rows(self, low=None) -> jax.Array:
        """(L, B, T, R) bf16: the reference's own latent rows of every
        position written, through the reference codec, T whole blocks;
        ``low``: computed in that precision."""
        mix, B, L, T0 = self.mix, self.mix["batch"], self.L, self.mix["context"]
        fam = byname.load("values", self.config["values"]["family"])
        C, S = mix["prefill_chunk"], mix["answer"]
        T = -(-(T0 + S) // BLOCK) * BLOCK
        parts = []
        for c in range(T0 // C):
            x = fam.context(self.config, self.seed, c, B, C)
            parts.append(jnp.stack([refmla.latent(self.params[i], self.d, x[i], c * C
                                                  + jnp.arange(C), low).astype(jnp.bfloat16)
                                    for i in range(L)]))
        xs = jnp.transpose(self.x[:, :, :, 0], (1, 2, 0, 3))        # (L, B, S, hidden)
        parts.append(jnp.stack([refmla.latent(self.params[i], self.d, xs[i], T0
                                              + jnp.arange(S), low).astype(jnp.bfloat16)
                                for i in range(L)]))
        parts.append(jnp.zeros((L, B, T - T0 - S, self.R), jnp.bfloat16))
        rows = jnp.concatenate(parts, axis=2)
        return jnp.stack([self._through_codec(rows[i], i) for i in range(L)])

    def _through_codec(self, rows: jax.Array, layer: int) -> jax.Array:
        w = gen.words16(rows).reshape(-1, self.f.page_words)
        n = w.shape[0]
        pad = -n % PAGE_BLOCK
        w = jnp.concatenate([w, jnp.zeros((pad, w.shape[1]), w.dtype)])
        bases, widths = (jnp.asarray(a) for a in self.tables[layer])
        back = jax.lax.map(lambda blk: refcodec.roundtrip(blk, bases, widths, self.f),
                           w.reshape(-1, PAGE_BLOCK, w.shape[1]))
        return gen.bf16_of(back.reshape(-1, w.shape[1])[:n]).reshape(rows.shape)

    def check(self, out: dict, limits: dict) -> tuple[dict, int]:
        return mla_check(self, out, limits)

    def control(self, out: dict) -> dict:
        return mla_control(self, out)


def _latent_gaps(words: np.ndarray, want: jax.Array) -> tuple[float, float]:
    """Rows the program holds, as bf16 words, against the reference's."""
    mine = np.asarray(gen.bf16_of(jnp.asarray(words)), np.float32).reshape(1, -1)
    gap, off = checks._gaps(mine, np.asarray(want, np.float32).reshape(1, -1))
    return float(gap.max()), float(off.max())


def mla_check(drv: Driver, out: dict, limits: dict) -> tuple[dict, int]:
    f, G, last = drv.f, drv.G, out["last"]
    numbers = {"kv_pages_off": 0, "kv_words_off": 0}
    failed = 0
    ref_rows = drv.reference_rows()                          # (L, B, T, R) bf16
    latent = []                                              # (gap, off) per row set
    for n, (i, b, g) in enumerate(out["groups"]):
        prog = out["pages"][n]
        bases, widths = drv.tables[i]
        dec = np.asarray(refcodec.decode({k: jnp.asarray(v) for k, v in prog.items()},
                                         bases, widths, f)).reshape(-1)
        wrong = int((out["words"][n] != dec).sum())
        bad = wrong > 0
        if (i, b, g) in drv.raw:
            raw = drv.raw[i, b, g].reshape(drv.gp, f.page_words)
            ref = refcodec.encode(jnp.asarray(raw), bases, widths, f)
            off = checks.pages_off(prog, {k: np.asarray(v) for k, v in ref.items()},
                                   checks.KV_PAGE_FIELDS, drv.gp)
            numbers["kv_pages_off"] += int(off.sum())
            bad |= bool(off.any())
        numbers["kv_words_off"] += wrong
        latent.append(_latent_gaps(out["words"][n], ref_rows[i, b, g * G:(g + 1) * G]))
        failed += int(bad)
    start = last - last % G                                   # the current group's raw rows
    for i, tail in enumerate(out["tail"]):
        latent += [_latent_gaps(tail[b], ref_rows[i, b, start:last + 1])
                   for b in range(tail.shape[0])]
    failed += sum(int(g > limits["latent_gap"] or o > limits["latent_off_pct"])
                  for g, o in latent)
    numbers["latent_gap"] = max(g for g, _ in latent)
    numbers["latent_off_pct"] = max(o for _, o in latent)
    for name, (gaps, offs) in zip(("mla", "attn"), _output_gaps(drv, out, ref_rows)):
        failed += sum(int(g > limits[f"{name}_gap"] or o > limits[f"{name}_off_pct"])
                      for g, o in zip(gaps, offs))
        numbers[f"{name}_gap"] = float(max(gaps))
        numbers[f"{name}_off_pct"] = float(max(offs))
    return checks.verdict(numbers, limits), failed


def _reference_outputs(drv: Driver, ref_rows: jax.Array, steps: list[int], low=None):
    """({step: (L, B, hidden)}, {step: (L, B, H, latent)}) float32: the
    reference's layer outputs and latent attention outputs at the given
    steps; ``low``: computed in that precision."""
    T0 = drv.mix["context"]
    qpos = T0 + jnp.asarray(steps)
    ys, lats = [], []
    for i in range(drv.L):
        xq = jnp.swapaxes(drv.x[jnp.asarray(steps), i, :, 0], 0, 1)     # (B, Q, hidden)
        y, lat = refmla.attend(drv.params[i], drv.d, xq, qpos, ref_rows[i], BLOCK, low)
        ys.append(np.asarray(y))
        lats.append(np.asarray(lat))
    return ({s: np.stack([y[:, n] for y in ys]) for n, s in enumerate(steps)},
            {s: np.stack([o[:, n] for o in lats]) for n, s in enumerate(steps)})


def _output_gaps(drv: Driver, out: dict, ref_rows: jax.Array):
    """(gaps, offs) of the layer outputs, then of the latent attention
    outputs: one per answer, a (layer, sequence) pair at a sampled step."""
    steps = sorted(out["outputs"])
    if not steps:
        raise RuntimeError("the window finished no sampled step")
    result = []
    for key, ref in zip(("outputs", "attn"), _reference_outputs(drv, ref_rows, steps)):
        gaps, offs = [], []
        for s in steps:
            want = ref[s].reshape(drv.L * drv.mix["batch"], -1)
            for o in out[key][s]:
                gap, off = checks._gaps(o.reshape(want.shape), want)
                gaps.extend(gap.tolist())
                offs.extend(off.tolist())
        result.append((gaps, offs))
    return result


def mla_control(drv: Driver, out: dict) -> dict:
    """The reference one precision below in the program's place: every
    latent row and layer output computed in float8 (e4m3) from float8
    weights and hidden states (``refmla``'s ``low``), the rows through the
    reference codec, and the sampled groups' pages encoded from them."""
    f, G, fp8 = drv.f, drv.G, jnp.float8_e4m3fn
    ctl = {"groups": out["groups"], "last": out["last"], "pages": [], "words": [],
           "dropped": out["dropped"]}
    low_rows = drv.reference_rows(fp8)
    for i, b, g in out["groups"]:
        bases, widths = drv.tables[i]
        words = gen.words16(low_rows[i, b, g * G:(g + 1) * G]).reshape(drv.gp, f.page_words)
        pages = refcodec.encode(words, bases, widths, f)
        ctl["pages"].append({k: np.asarray(pages[k]) for k in checks.KV_PAGE_FIELDS})
        ctl["words"].append(np.asarray(words).reshape(-1))
    last = out["last"]
    ctl["tail"] = [_words(low_rows[i, :, last - last % G:last + 1]).reshape(low_rows.shape[1], -1)
                   for i in range(drv.L)]
    steps = sorted(out["outputs"])
    ref, lat = _reference_outputs(drv, low_rows, steps, fp8)
    ctl["outputs"] = {s: [ref[s][:, :, None]] for s in steps}
    ctl["attn"] = {s: [lat[s][:, :, None]] for s in steps}
    return ctl
