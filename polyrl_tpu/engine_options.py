"""What a ``CBEngine`` can be told, and what it is told by default.

The one declaration of the engine's options. ``CBEngine(cfg, params,
**options)`` builds an :class:`EngineOptions` from its keywords;
``rollout.serve.create_server`` forwards its ``**engine_options`` there;
``config.RolloutSection`` inherits the fields (the YAML keys stay flat:
``rollout.max_slots``) and projects them back with ``engine_options()``;
``rollout.serve``'s command line is generated from them
(:func:`add_engine_flags`). Adding an option is an edit to this class.

No JAX here: ``config.py`` and argument parsing import this module.
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass


def _opt(default, help: str):
    """A field whose ``help`` is both its documentation and the text of its
    ``rollout.serve`` flag."""
    return dataclasses.field(default=default, metadata={"help": help})


@dataclass(frozen=True)
class EngineOptions:
    max_slots: int = _opt(64, "sequences decoded side by side")
    page_size: int = _opt(64, "tokens per KV page")
    max_seq_len: int = _opt(16384, "longest prompt plus answer a slot holds")
    num_pages: int | None = _opt(
        None, "KV pool pages (default: derived from slots and length, "
              "enough for half the slots at full length)")
    prompt_buckets: tuple[int, ...] = _opt(
        (128, 256, 512, 1024, 2048, 4096),
        "prompt-length padding buckets (page-aligned)")
    steps_per_dispatch: int = _opt(
        8, "fused decode steps per device dispatch: divides dispatch and "
           "fetch overhead by k at the cost of <=(k-1) wasted device "
           "iterations per finished slot and up to k steps of abort and "
           "admission latency")
    # Needs ~2*ceil(fetch RTT / per-dispatch compute): the fetcher pulls
    # the oldest half-window per round trip while the newer half computes.
    # 16 was sized for a ~300 ms fetch RTT at ~40 ms/dispatch; not re-tuned
    # on a directly attached chip. Costs up to this many run-ahead
    # dispatches after the last slot finishes (near-free on device: the
    # step no-ops via lax.cond when nothing is active).
    pipeline_depth: int = _opt(
        16, "run-ahead dispatch window of the fetcher-thread pipeline "
            "(0 = drain every dispatch); lower it for tighter abort "
            "latency on colocated time-sliced workers. While a request "
            "waits for pages or a slot the engine runs one program "
            "ahead of the device instead, whatever this says")
    prefill_chunk: int = _opt(
        0, "chunked prefill: prompts longer than this prefill one "
           "page-aligned chunk per engine iteration, interleaved with "
           "decode, so a long admission cannot stall every running "
           "stream (0 = off, whole-prompt dispatches)")
    prefill_first: bool = _opt(
        False, "while a chunked prefill is under way, hold the decode "
               "dispatches: the admitted batch is whole soonest and its "
               "streams stall meanwhile (off = one chunk, then one decode "
               "dispatch, in turn)")
    prefix_pages_floor: int = _opt(
        1, "smallest bucket of prefix pages a suffix-attending prefill "
           "(cache hit, chunk extend, chunk final) is built for; the "
           "buckets double from it. A higher floor builds fewer programs "
           "(a model of many unrolled layers takes tens of seconds each) "
           "and attends over padded pages under the floor")
    # Proposals, token history and acceptance stay on the device, so spec
    # dispatches pipeline like normal steps. Wins when outputs are locally
    # repetitive (math/code CoT); costs m x attention reads per verify, so
    # it trades against very long contexts.
    spec_tokens: int = _opt(
        0, "prompt-lookup speculative decoding: verify this many "
           "ngram-proposed draft tokens per decode dispatch, up to N+1 "
           "tokens per weight read, distribution-exact rejection "
           "sampling (0 = off)")
    spec_rounds: int = _opt(
        2, "fused device-side speculation rounds per dispatch (proposals "
           "and acceptance never leave the chip)")
    # ARCHITECTURE.md "Token-level continuous generation": the manager
    # forwards per-token progress and a mid-stream resume re-issues only
    # the suffix; the decoded pages are published to the prefix cache so a
    # continuation landing back here re-uses the KV.
    salvage_partials: bool = _opt(
        True, "aborts, preemptions and shutdowns drain the run-ahead "
              "pipeline into the stream as a partial instead of dropping "
              "decoded tokens (off = fastest abort, resume from token 0)")
    # admission scheduler geometry (ARCHITECTURE.md "Group-shared prefill")
    admit_wave: int = _opt(
        8, "max admissions fused into one batched prefill dispatch")
    admit_reorder_window: int = _opt(
        8, "blocked queue heads admission may skip past while forming a "
           "wave (a sibling waiting for its leader's publish, a prefix "
           "hit amid a fresh wave, a chunk-bound prompt); 0 = strict FIFO "
           "head-of-line")
    group_share: bool = _opt(
        True, "prefill a GRPO group's shared prompt once and batch-attach "
              "the siblings to the published pages (off = per-request "
              "singleton suffix admission)")
    # ARCHITECTURE.md "Shared-prefix decode attention": ONE HBM stream of
    # the group's shared prompt KV serves all siblings (phase 1), each
    # slot's own suffix pages merge in via the flash LSE (phase 2).
    # Singletons always take the per-slot kernel.
    decode_group_share: bool = _opt(
        True, "decode dispatches with live GRPO groups route through the "
              "two-phase grouped paged-attention kernel (off = every "
              "sibling re-streams the group's prompt KV per decode step)")
    group_preref_ttl_s: float = _opt(
        30.0, "sibling-wait pre-ref expiry: how long a leader's pre-taken "
              "prefix refs survive waiting for siblings that never arrive "
              "(dropped groups, mis-sized hints) before the sweep "
              "releases them")
    # ARCHITECTURE.md "KV memory plane": feeds the ``memory`` statusz
    # section, the ``engine/kv_{hot,warm,cold}_page_frac`` gauges and HBM
    # attribution. The ledger never touches RNG, device state or
    # scheduling: engine output is bitwise identical either way.
    kv_ledger: bool = _opt(
        True, "per-page KV residency and lifetime ledger (off also "
              "disables spilling)")
    kv_cold_after_dispatches: int = _opt(
        256, "idle age (decode dispatches since last touch) past which a "
             "resident KV page counts as cold (warm = a quarter of this)")
    # rollout/kvspill.py; ARCHITECTURE.md "KV spill tier": sessions
    # oversubscribe HBM instead of losing their KV to eviction. Needs the
    # ledger (candidate ranking, reconciliation) and the prefix cache (the
    # spillable population).
    kv_spill: bool = _opt(
        True, "host-RAM KV spill tier: cold unreferenced published "
              "prefix-cache pages page out of HBM under watermark "
              "pressure and restore on a prefix hit (off = capacity "
              "eviction destroys them)")
    kv_spill_host_gb: float = _opt(
        4.0, "host-side capacity of the KV spill tier, GB")
    # hysteresis: the gap is what keeps demand restores from re-arming the
    # sweep page by page (spill/restore thrash)
    kv_spill_high_watermark: float = _opt(
        0.92, "page utilisation at which the spill sweep arms")
    kv_spill_low_watermark: float = _opt(
        0.80, "page utilisation the spill sweep spills down toward")
    # obs/engine_profile.py; ARCHITECTURE.md "Engine-loop profiler": the
    # profiler never touches RNG, device state or scheduling, only clocks
    # around them: sampled output is identical either way.
    loop_profile: bool = _opt(
        True, "engine-loop profiler behind the engine.loop statusz block, "
              "the device_frac/accounting_frac gauges and "
              "tools/engine_report.py")

    def __post_init__(self):
        # argparse's nargs="+" and YAML hand over lists
        object.__setattr__(self, "prompt_buckets",
                           tuple(self.prompt_buckets))
        if any(b % self.page_size for b in self.prompt_buckets):
            raise ValueError("prompt buckets must be page-aligned")
        if self.prefill_chunk < 0 or self.prefill_chunk % self.page_size:
            # -8 % 8 == 0 would let a negative (still truthy) chunk size
            # enable chunking
            raise ValueError(
                f"prefill_chunk must be a non-negative multiple of "
                f"page_size={self.page_size}, got {self.prefill_chunk}")
        if self.spec_tokens < 0:
            raise ValueError(
                f"spec_tokens must be >= 0, got {self.spec_tokens}")
        if self.spec_rounds < 1:
            raise ValueError(
                f"spec_rounds must be >= 1, got {self.spec_rounds}")
        if not (0.0 < self.kv_spill_low_watermark
                <= self.kv_spill_high_watermark <= 1.0):
            raise ValueError(
                f"kv spill watermarks must satisfy 0 < low <= high <= 1, "
                f"got low={self.kv_spill_low_watermark} "
                f"high={self.kv_spill_high_watermark}")


OPTION_NAMES = tuple(f.name for f in dataclasses.fields(EngineOptions))


def add_engine_flags(parser: argparse.ArgumentParser) -> None:
    """One flag per option, ``dest`` the option's name: ``--max-slots N``;
    a switch that is on by default is turned off by ``--no-<name>``."""
    for f in dataclasses.fields(EngineOptions):
        flag = f.name.replace("_", "-")
        help_ = f.metadata["help"]
        if isinstance(f.default, bool):
            parser.add_argument(
                f"--no-{flag}" if f.default else f"--{flag}", dest=f.name,
                action="store_false" if f.default else "store_true",
                default=f.default,
                help=("turn off: " if f.default else "") + help_)
        elif isinstance(f.default, tuple):
            parser.add_argument(
                f"--{flag}", type=int, nargs="+", default=f.default,
                help=f"{help_} (default {' '.join(map(str, f.default))})")
        else:
            # num_pages: None means "derived", and when given is an int
            parser.add_argument(
                f"--{flag}", default=f.default,
                type=int if f.default is None else type(f.default),
                help=(help_ if f.default is None
                      else f"{help_} (default {f.default})"))


def options_of(holder) -> dict:
    """The options as ``holder`` has them under their own names (a config
    section, flags parsed by :func:`add_engine_flags`), checked: keywords
    for ``CBEngine`` and ``create_server``."""
    return dataclasses.asdict(EngineOptions(
        **{name: getattr(holder, name) for name in OPTION_NAMES}))
