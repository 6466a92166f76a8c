"""All of a first token's time that lies OUTSIDE the replica's submit ->
first token, over the whole window: the mean of the client's first-token
times (from the time a request was due) less the mean by which the client
sent late, less `replica_ttft_ms`. What is left is the way in (socket,
proxy, handle, the actor call, the deployment's own code before `submit`)
and the way back (the first token waiting for a pull, the pull's reply,
the proxy's write, the socket). None when a request failed: its first-token
time is a stand-in, not a measurement."""
from benchmark.common import hist_mean_ms


def read(facts):
    if facts["kind"] != "serve":
        return None
    cl = facts["client"]
    inside = hist_mean_ms(facts, "ttft")
    if inside is None or cl["failed"] or not cl["ttft_ms"] or not cl["late_ms"]:
        return None
    ttft, late = cl["ttft_ms"], cl["late_ms"]
    return sum(ttft) / len(ttft) - sum(late) / len(late) - inside
