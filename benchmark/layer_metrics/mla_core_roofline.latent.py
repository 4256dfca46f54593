"""Share of its roofline the scope ``mla_core`` reaches in a decode step
of a model that is latent attention in every layer: the least time of the
absorbed attention over the contexts at the traced part's middle (the
client's token count), the larger of its latent bytes (1,152 B a token a
layer at dots.vlm1's sizes) over the chip's published bandwidth and its
FLOPs (278,528 a token a layer at 128 heads) over the chip's published
peak (``costs_latent.mla_core_least``), divided by ``mla_core_ms``. Which
roof bound it, and whether the program's own count of rows
(``mla_rows_read``) agrees with the client's to 2%, go into ``checks``
(``mla_core_bound_by``, ``mla_rows``). The pool's rows are padded to whole
lanes, the value product runs over the whole row and the new row's write
is left out, so it reads low, never high. None without a latent key or a
trace. Layer: forward pass and kernels. Moves: rollout_tok_s."""

from benchmark.lib import costs_latent, harness


def read(obs):
    c = obs["config"]["config"]
    if obs["peaks"] is None or not costs_latent.is_latent(c):
        return None
    kv_mid = costs_latent.kv_tokens_mid(obs)
    core_ms = harness.load_reader("mla_core_ms")(obs)
    if kv_mid is None or core_ms is None:
        return None
    least_s, roof = costs_latent.mla_core_least(c, kv_mid, obs["peaks"])
    obs["checks"]["mla_core_bound_by"] = roof
    agree = costs_latent.rows_agree(obs)
    if agree is not None:
        obs["checks"]["mla_rows"] = agree
    return 100.0 * least_s / (core_ms / 1e3)
