"""The GBDI-FR page format as a configuration file states it, and the
bytes the algorithm needs per page.

Everything here is computed from the configuration's ``format`` group
alone, so a change to the program's defaults never moves the yardstick.
"""
from __future__ import annotations

import dataclasses

LANE_WIDTHS = (1, 2, 4, 8, 16)


@dataclasses.dataclass(frozen=True)
class Format:
    word_bits: int
    page_words: int
    num_bases: int
    width_set: tuple[int, ...]
    bucket_caps: tuple[int, ...]
    outlier_cap: int

    @classmethod
    def from_config(cls, config: dict) -> "Format":
        f = config["format"]
        return cls(word_bits=int(f["word_bits"]), page_words=int(f["page_words"]),
                   num_bases=int(f["num_bases"]), width_set=tuple(f["width_set"]),
                   bucket_caps=tuple(f["bucket_caps"]), outlier_cap=int(f["outlier_cap"]))

    @property
    def ptr_bits(self) -> int:
        """Pointer width: the narrowest lane-packable width that holds the
        bases plus the zero and outlier codes."""
        need = (self.num_bases + 1).bit_length()
        return next(b for b in LANE_WIDTHS if b >= need)

    @property
    def zero_code(self) -> int:
        return self.num_bases

    @property
    def outlier_code(self) -> int:
        return self.num_bases + 1

    @property
    def ptr_lanes(self) -> int:
        return self.page_words * self.ptr_bits // 32

    @property
    def class_lanes(self) -> tuple[int, ...]:
        return tuple(cap * w // 32 for w, cap in zip(self.width_set, self.bucket_caps))

    @property
    def delta_lanes(self) -> int:
        return sum(self.class_lanes)

    @property
    def raw_bytes_per_page(self) -> int:
        return self.page_words * self.word_bits // 8

    @property
    def blob_bytes_per_page(self) -> int:
        """Serialized bytes of one page: packed pointers and deltas, the
        outlier values at word size, their 16-bit indices and a 4-byte
        header (outlier count)."""
        return (4 * (self.ptr_lanes + self.delta_lanes)
                + self.outlier_cap * self.word_bits // 8
                + self.outlier_cap * 2 + 4)

    @property
    def page_bytes_moved(self) -> int:
        """Necessary HBM bytes of one page through encode (or decode): the
        raw page in (out) and its serialized blob out (in)."""
        return self.raw_bytes_per_page + self.blob_bytes_per_page
