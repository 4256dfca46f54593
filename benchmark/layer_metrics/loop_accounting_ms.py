"""Host milliseconds a decode dispatch the engine's loop thread spends on its
ledgers: ``accounting`` (flight deck, page ledger, page growth, dispatch
bookkeeping) and ``spill_sweep``. Delta ``phase_accounting_s`` +
``phase_spill_sweep_s`` over delta ``decode_dispatches`` of ``GET
/get_server_info``, first to last sample. A part of ``loop_host_ms``. None
for an engine without the counters. Layer: CBEngine loop. Moves:
rollout_tok_s."""

from benchmark.lib import phases


def read(obs):
    return phases.ms_a_dispatch(obs, 'accounting', 'spill_sweep')
