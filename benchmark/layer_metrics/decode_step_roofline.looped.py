"""Share of the HBM roofline one decode step of a looped model reaches:
the least bytes the step needs (``costs_looped.decode_step_bytes``: the
stack's weights once a PASS, the head once, every pass's keys and values
of the contexts at the traced part's middle, the passes as the program
counted them) over the chip's published bandwidth, divided by
``decode_step_ms``. Whether the program's own count of keys
(``kv_pass_rows_read``) agrees with the client's to 2% goes into
``checks`` (``pass_rows``). None without the engine's ``ut_passes``, the
family's keys or a trace. Layer: forward pass and kernels. Moves:
rollout_tok_s."""

from benchmark.lib import costs_looped, harness


def read(obs):
    c = obs["config"]["config"]
    if obs["peaks"] is None or not costs_looped.is_looped(c):
        return None
    rows = costs_looped.pass_rows_mid(obs)
    step_ms = harness.load_reader("decode_step_ms")(obs)
    if rows is None or step_ms is None:
        return None
    agree = costs_looped.rows_agree(obs)
    if agree is not None:
        obs["checks"]["pass_rows"] = agree
    least_s = costs_looped.decode_step_bytes(c, rows) / obs["peaks"]["bytes"]
    return 100.0 * least_s / (step_ms / 1e3)
