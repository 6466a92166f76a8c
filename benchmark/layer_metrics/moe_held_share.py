"""Share of the router's (token, expert) pairs that fall on experts HELD by
this chip, in percent, over the window's recorded `engine.decode` spans
that carry both counts: sum(`moe_pairs_held`) / sum(`moe_pairs`).
`moe_pairs` is what the live slots' router chose over all routed experts
(slots x experts per token x expert layers), `moe_pairs_held` those whose
expert this replica holds — the rows its grouped matmuls multiply. A chip
that holds 40 of 160 experts reads 25 under even routing; the rest is what
the deployment's other chips compute. A program without the second count
(every expert held: no share to read) gives None."""
from benchmark import span_reduce


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    steps = [s.stats for s in tr.named("engine.decode")
             if "moe_pairs" in s.stats and "moe_pairs_held" in s.stats]
    pairs = sum(float(s["moe_pairs"]) for s in steps)
    if not pairs:
        return None
    return 100.0 * sum(float(s["moe_pairs_held"]) for s in steps) / pairs
