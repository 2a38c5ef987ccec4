"""Traffic kind ``decode``: the program's ``serving.engine.KVSession``
over one attention layer's compressed KV cache.  A batch of distinct
contexts is prefilled in set-up; then closed-loop ``step`` calls, each
blocked until its attention output is ready; after ``answer`` steps the
batch restarts from the post-prefill cache.  Checked on the flushed pages
and decoded rows of sampled positions and on the attention outputs of
sampled steps, every time the window ran them."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import cells
import checks
import gen
from fmt import Format

span = jax.profiler.TraceAnnotation


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        self.config, self.mix, self.seed = config, mix, seed
        self.f = Format.from_config(config)
        kv = config["kv"]
        self.row_words = kv["n_kv"] * kv["head_dim"]
        if self.row_words % self.f.page_words:
            raise ValueError("a KV row must fill whole pages")
        self.ppr = self.row_words // self.f.page_words

    def setup(self) -> None:
        from repro.serving.engine import KVSession
        from repro.serving.kv_cache import KVSpec

        mix, kv = self.mix, self.config["kv"]
        self.inp = jax.block_until_ready(
            gen.family(self.config).decode_inputs(self.config, mix, self.seed))
        self.bases, self.widths = cells.config_table(self.config, self.f)
        spec = KVSpec(n_kv=kv["n_kv"], head_dim=kv["head_dim"], max_len=mix["max_len"],
                      fr=cells.fr_config(self.f), resident_decode=True)
        self.sess = KVSession(spec, mix["batch"], cells.base_table(self.bases, self.widths))
        self.sess.prefill(self.inp["k_ctx"], self.inp["v_ctx"])
        self.post = jax.block_until_ready(self.sess.cache)
        self.steps = [tuple(self.inp[n][i] for n in ("q", "k", "v"))
                      for i in range(mix["answer"])]
        jax.block_until_ready(self.sess.step(*self.steps[0]))   # compiles append + attend
        self.restart()
        rng = gen.host_rng(self.seed, 3)
        S = mix["answer"]
        pick = rng.choice(S - 1, min(S - 1, mix["check_steps"] - 1), replace=False)
        self.sample_steps = sorted({*pick.tolist(), S - 1})      # the longest is always in
        self.kept: dict[int, list] = {i: [] for i in self.sample_steps}

    def restart(self) -> None:
        self.sess.cache = self.post
        self.sess.pos = self.mix["context"]

    def window(self, seconds: float) -> dict:
        S, steps = self.mix["answer"], 0
        t0 = time.perf_counter()
        with span("bench.window"):
            done = False
            while not done:
                for i in range(S):
                    with span("bench.step"):
                        out = jax.block_until_ready(self.sess.step(*self.steps[i]))
                    steps += 1
                    if i in self.kept:
                        self.kept[i].append(out)
                    if time.perf_counter() - t0 >= seconds:
                        done = True
                        break
                else:
                    self.restart()
        elapsed = time.perf_counter() - t0
        B = self.mix["batch"]
        return {"elapsed": elapsed, "attempted": steps * B,
                "metrics": {"decode_tokens_per_s": steps * B / elapsed},
                "work": {"steps": steps}}

    def raw_row(self, b: int, t: int, name: str) -> jax.Array:
        T0 = self.mix["context"]
        if t < T0:
            return self.inp[f"{name}_ctx"][b, t]
        return self.inp[name][t - T0][b, 0]

    def outputs(self) -> dict:
        mix, cache = self.mix, self.sess.cache
        last = self.sess.pos - 1                      # the last position written
        rng = gen.host_rng(self.seed, 4)
        B = mix["batch"]
        rows = {(int(b), int(t)) for b, t in zip(rng.integers(0, B, mix["check_rows"]),
                                                  rng.integers(0, last + 1, mix["check_rows"]))}
        rows |= {(b, last) for b in range(B)}
        rows = sorted(rows)
        bi = np.asarray([b for b, _ in rows])
        ti = np.asarray([t for _, t in rows])
        out = {"rows": rows, "last": last}
        for name in ("k", "v"):
            dec = cache[f"{name}_dec"][bi, ti]                       # (n, Kv, hd) bf16
            out[f"{name}_words"] = np.asarray(gen.words16(dec)).reshape(len(rows), -1)
            slots = ti[:, None] * self.ppr + np.arange(self.ppr)[None, :]
            out[f"{name}_pages"] = {k: np.asarray(v[bi[:, None], slots])
                                    for k, v in cache[f"{name}_pages"].items()}
        out["attn"] = {i: [np.asarray(o.astype(jnp.float32)) for o in outs]
                       for i, outs in self.kept.items() if outs}
        del self.sess, self.post, self.kept
        return out

    def check(self, out: dict, limits: dict) -> tuple[dict, int]:
        return checks.decode(self, out, limits)

    def control(self, out: dict) -> dict:
        return checks.decode_control(self, out)
