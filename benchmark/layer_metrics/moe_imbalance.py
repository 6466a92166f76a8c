"""How unevenly the router loads the experts in the decode steps: over the
window's recorded `engine.decode` spans that carry the two attributes,
sum(`moe_hottest`) x num_experts / sum(`moe_pairs`). `moe_pairs` is the
step's routed (token, expert) pairs (live slots x experts per token x
layers), `moe_hottest` the load of the step's fullest expert summed over
the layers: 1.0 is an even spread, num_experts / experts-per-token (8 here)
every token on the same experts. The fullest expert's rows are the longest
run the grouped matmul has to walk."""
from benchmark import span_reduce


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None or tr.cell is None:
        return None
    steps = [s.stats for s in tr.named("engine.decode")
             if "moe_pairs" in s.stats and "moe_hottest" in s.stats]
    pairs = sum(float(s["moe_pairs"]) for s in steps)
    _, conf = span_reduce.shapes(tr.cell)
    if not pairs or "num_experts" not in conf:
        return None
    hottest = sum(float(s["moe_hottest"]) for s in steps)
    return hottest * conf["num_experts"] / pairs
