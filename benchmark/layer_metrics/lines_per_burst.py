"""Lines the server writes a burst: a stream's handler thread takes what its
queue holds, serialises each line and flushes them as one chunk. Delta
``stream_lines`` over delta ``stream_chunks`` of ``GET /get_server_info``,
first to last sample. The engine puts a line a token, so a burst of a whole
dispatch holds its fused steps (8) and a handler that wakes while the loop
still emits writes fewer; an engine that puts a line a dispatch would read
1 for the same tokens. None for a server without the counter. Layer:
manager and server. Moves: rollout_tok_s."""

from benchmark.lib import counters


def read(obs):
    return counters.delta_ratio(obs, "stream_lines", "stream_chunks")
