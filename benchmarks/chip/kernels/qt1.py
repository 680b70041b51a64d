"""Work of one QT1 serve step, counted from its shapes.

The (f, s, t) join of a batch of B queries reads, for each of its K key
rows of L posting slots, the anchor position g and the fragment bounds
lo and hi (three int32 per slot), plus two float32 per query (idf sum,
span adjustment), and writes top_k (score, g, lo, hi) results per query.
These are the raw posting rows the join has to consider and the results
it writes, whatever payload or kernel carries them.

The bound is memory (HBM bandwidth): the join is integer compares and
gathers with no matrix unit work, and no peak for the chip's vector
integer rate is published, so no operation count enters the roofline.
"""


def work(B: int, L: int, serve: dict) -> dict:
    K = serve["k_fst"]
    top_k = serve["top_k"]
    read = B * K * L * 3 * 4 + B * 2 * 4
    write = B * min(top_k, L) * 4 * 4
    return {"bytes": read + write, "bound": "memory"}
