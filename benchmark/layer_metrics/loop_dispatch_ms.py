"""Host milliseconds a decode dispatch the engine's loop thread spends inside
the dispatch calls: device-state upload and the fused step's enqueue
(``decode_dispatch_device``) and prefill, attach and chunk dispatches
(``prefill_dispatch``). Delta ``phase_decode_dispatch_s`` +
``phase_prefill_dispatch_s`` over delta ``decode_dispatches`` of ``GET
/get_server_info``, first to last sample. A part of ``loop_host_ms``. None
for an engine without the counters. Layer: CBEngine loop. Moves:
rollout_tok_s."""

from benchmark.lib import phases


def read(obs):
    return phases.ms_a_dispatch(obs, 'decode_dispatch', 'prefill_dispatch')
