"""Share of its roofline the scope ``sparse_select`` reaches in a decode
step: the pooled keys the step's rows were scored against
(``costs_sala.select_bytes`` of the count the program kept on the device,
``sparse_pooled_scored``: a pooled key of both K/V heads, float32, 1,024
B; (context - 32) / 16 + 1 of them a row and sparse layer), the larger of
those bytes over the chip's published bandwidth and the scores' FLOPs over
its published peak, divided by ``sparse_select_ms``. The stage is bound by
latency, not by bytes (a choice of 64 among a few hundred values a row and
head): the share says how far. None without the counter, the family's keys
or a trace. Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_sala, harness


def read(obs):
    if obs["peaks"] is None:
        return None
    scored = costs_sala.counted_per_step(obs, "sparse_pooled_scored")
    ms = harness.load_reader("sparse_select_ms")(obs)
    if scored is None or ms is None:
        return None
    c = obs["config"]["config"]
    least_s = costs_sala.least_seconds(
        obs["peaks"], costs_sala.select_bytes(c, scored),
        costs_sala.select_flops(c, scored))
    return 100.0 * least_s / (ms / 1e3)
