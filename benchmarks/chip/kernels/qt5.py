"""Work of one QT5 serve step (the nearest-r window join), counted from
its shapes.

A batch of B queries reads the anchor's ordinary posting row (L int32
positions), Kn other non-stop rows (L int32 each) with their
multiplicities, and Ks stop-lemma aggregate rows aligned with the
anchor (count and nearest offset, two int32 per slot) with theirs, plus
two float32 per query, and writes top_k (score, g, lo, hi) results per
query. QT3/QT4 traffic that rides the QT5 executables (zero stop rows)
is counted at the same shapes, since the step reads them all.

The bound is memory (HBM bandwidth): the join is integer compares and
counts with no matrix unit work, and no peak for the chip's vector
integer rate is published, so no operation count enters the roofline.
"""


def work(B: int, L: int, serve: dict) -> dict:
    Kn = serve["k_ns"]
    Ks = serve["k_st"]
    top_k = serve["top_k"]
    read = B * L * 4 + B * Kn * (L * 4 + 4) + B * Ks * (L * 8 + 4) + B * 2 * 4
    write = B * min(top_k, L) * 4 * 4
    return {"bytes": read + write, "bound": "memory"}
