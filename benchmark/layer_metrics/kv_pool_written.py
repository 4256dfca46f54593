"""Share of the KV pool's tokens that hold keys and values when the window
ends: the context of every admitted request (its prompt plus what it has
generated, ``kv_tokens_at_end``, counted by the client) over the tokens the
pool's pages hold. ``kv_pool_fill`` is what admission has reserved; the
distance between the two is pool that is spoken for and not yet written.
Only for traffic without shared prefixes, where a token of context is a
token of the pool. Layer: KV manager. Moves: rollout_tok_s."""


def read(obs):
    held, pool = obs.get("kv_tokens_at_end"), obs.get("kv_pool_tokens")
    if not held or not pool:
        return None
    return 100.0 * held / pool
