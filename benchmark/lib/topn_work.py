"""The least bytes a TopN candidate scoring has to read, from what it
scored: what ``topn_hbm_share`` divides by the kernel's device time.  Kept
with the benchmark, beside ``lib/work.py``, so that no PR that claims a
gain in the kernel can change what it is held to."""

from __future__ import annotations


def topn_min_bytes(words: int, slice_words: int) -> int:
    """Bytes a scoring of a fragment's candidates must read from HBM at
    least once: each of the candidates' ``words`` 32-bit words that are
    not zero (the ``words`` tag of its ``topn.score`` span: a word that is
    zero adds nothing to a count, so a scorer may skip it, and it cannot
    skip one that is not), and the src row's plane of ``slice_words`` words.
    Whatever form, index or layout the program keeps them in, it reads
    no less (one that reads a word's position, or the zeros of a dense row,
    reads more, and its share is the lower for it), so a share computed from
    this cannot pass 100%.  The counts it writes, 4 bytes a candidate, are
    left out."""
    return 4 * (int(words) + int(slice_words))
