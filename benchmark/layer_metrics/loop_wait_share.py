"""Share of the traced window the engine's loop thread lies inside its two
waits, ``engine/sample_fetch`` (blocked on the fetcher) and ``engine/idle``:
the program's own ``TraceAnnotation`` spans on the host line of the device
trace, on the device's clock. High means the host keeps ahead of the
device. The loop thread is the one that holds
``engine/decode_dispatch_device`` spans. Layer: CBEngine loop. Moves:
rollout_tok_s."""

from benchmark.lib import tracered, xspans

WAITS = ("engine/sample_fetch", "engine/idle")


def read(obs):
    trace = xspans.load()
    if not trace or trace["window"] is None:
        return None
    lo, hi = trace["window"]
    for spans in xspans.host_spans(trace, "engine/").values():
        if any(n == "engine/decode_dispatch_device" for n, _a, _b in spans):
            waits = [(a, b) for n, a, b in spans if n in WAITS]
            return 100.0 * tracered.union_length(waits) / (hi - lo)
    return None
