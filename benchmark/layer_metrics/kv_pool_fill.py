"""Mean share of the KV pool's pages that were allocated, over the window:
``page_util`` of ``GET /get_server_info`` (the page ledger's pages in use
over pages, at the last decode dispatch), sampled twice a second. Pages
are reserved for a request's whole budget at admission, so this is what
admission sees, not the KV that attention reads. Layer: KV manager.
Moves: rollout_tok_s."""

from benchmark.lib import stats


def read(obs):
    xs = [s["page_util"] for s in obs.get("server_info", [])
          if "page_util" in s]
    return 100.0 * stats.mean(xs) if xs else None
