"""Traffic kind ``roundtrip``: a chip-resident page stream; each round
calls the program's ``kernels.ops.encode_pages`` on every page, blocks,
calls ``kernels.ops.decode_pages`` on every blob, and blocks.  Checked on
pages of the last round drawn from the seed."""
from __future__ import annotations

import time

import jax
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

    def setup(self) -> None:
        from repro.kernels import ops

        self.ops, self.cfg = ops, cells.fr_config(self.f)
        self.pages = jax.block_until_ready(gen.stream(self.config, self.mix, self.seed))
        self.bases, self.widths = cells.config_table(self.config, self.f)
        self.table = cells.base_table(self.bases, self.widths)
        self.blob = self.dec = None
        self.round()                                  # compiles both directions

    def round(self) -> None:
        self.blob = self.dec = None                   # free the last round's outputs
        with span("bench.encode"):
            self.blob = jax.block_until_ready(
                self.ops.encode_pages(self.pages, self.table, self.cfg))
        with span("bench.decode"):
            self.dec = jax.block_until_ready(
                self.ops.decode_pages(self.blob, self.table, self.cfg))

    def window(self, seconds: float) -> dict:
        rounds, times = 0, []
        t = t0 = time.perf_counter()
        with span("bench.window"):
            while True:
                with span("bench.round"):
                    self.round()
                rounds += 1
                now = time.perf_counter()
                times.append(now - t)
                t = now
                if now - t0 >= seconds:
                    break
        elapsed = time.perf_counter() - t0
        n = self.pages.shape[0]
        raw = n * self.f.raw_bytes_per_page
        return {"elapsed": elapsed, "attempted": rounds, "times": times,
                "metrics": {"roundtrip_GiBps": rounds * raw / elapsed / 2**30},
                "work": {"encode_bytes": n * self.f.page_bytes_moved,
                         "decode_bytes": n * self.f.page_bytes_moved}}

    def outputs(self) -> dict:
        n = self.pages.shape[0]
        rows = np.sort(gen.host_rng(self.seed, 2).choice(
            n, min(n, self.mix["check_pages"]), replace=False))
        out = {"x": np.asarray(self.pages[rows]),
               "blob": {k: np.asarray(v[rows]) for k, v in self.blob.items()},
               "dec": np.asarray(self.dec[rows])}
        del self.pages, self.blob, self.dec
        return out

    def check(self, out: dict, limits: dict) -> tuple[dict, int]:
        return checks.roundtrip(out, self.bases, self.widths, self.f, limits)

    def control(self, out: dict) -> dict:
        return checks.roundtrip_control(out, self.bases, self.widths, self.f)
