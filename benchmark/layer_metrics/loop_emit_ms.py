"""Host milliseconds a decode dispatch the engine's loop thread spends in
``emit``: streaming fetched tokens to the request queues (one ``StreamLine``
and one ``queue.put`` a token), host mirrors, finalize folds. Delta
``phase_emit_s`` over delta ``decode_dispatches`` of ``GET
/get_server_info``, first to last sample. With ``loop_accounting_ms``,
``loop_dispatch_ms`` and ``loop_other_ms`` it partitions ``loop_host_ms``.
None for an engine without the counter. Layer: CBEngine loop. Moves:
rollout_tok_s."""

from benchmark.lib import phases


def read(obs):
    return phases.ms_a_dispatch(obs, 'emit')
