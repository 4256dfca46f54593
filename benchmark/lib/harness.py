"""What every cell shares: finding the cell's files by name, refusing a
machine without the chips, the compile cache and the compile counter, the
profiler window, and the verdict ``correct``.

Everything that belongs to one configuration, mix, plane, pattern, length
distribution, reference or per-layer metric is a file of its own under
``benchmark/<kind>/`` that ``load_named`` finds by the name in the data:
a new one is a new file, and no file that is there is edited."""

from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import sys
import threading
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
WORK_DIR = os.path.join(ROOT, ".bench_work")

# published key of a configuration file -> field of the program's ModelConfig
MODEL_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_word_embeddings", "qk_norm": "use_qk_norm",
    "attention_bias": "attention_bias",
    "max_position_embeddings": "max_position_embeddings",
}
# keys of a configuration file that are not sizes of the model
NOT_SIZES = ("name", "source", "reduced", "assumed", "deployment", "run")
# jax.monitoring events that mean "a program was built just now"
TRACE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


CHILDREN: list = []   # processes this run started (the manager)


# a compile of a step program takes tens of seconds; an eager one-operation
# program that host code makes for a new shape takes milliseconds
MAX_COMPILE_S_IN_WINDOW = 0.5


class Refused(Exception):
    """The run cannot be a measurement (no chip, unknown cell, ...)."""


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict]:
    """(cell, configuration file, benchmark) for a ``workloads`` name."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; it has "
                      f"{sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    return cell, load_config(os.path.join(ROOT, files[cell["config"]])), bench


def load_config(path: str) -> dict:
    """A configuration file holds the published sizes at its top level,
    under the source's own keys, and under ``run`` what the harness needs
    to run it (preset, reference, dtype, pool, limits of ``correct``).
    Returns ``run``'s keys with the sizes under ``config``."""
    with open(path) as f:
        raw = json.load(f)
    sizes = {k: v for k, v in raw.items() if k not in NOT_SIZES}
    return {**raw["run"], "config": sizes, "reduced": raw["reduced"]}


def model_overrides(config: dict) -> dict:
    """The configuration file's sizes as overrides of the program's preset,
    so that what runs is what the file says."""
    return {MODEL_FIELDS[k]: v for k, v in config["config"].items()
            if k in MODEL_FIELDS}


def cell_metrics(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The cell's ``end_to_end`` or ``per_layer`` metrics: those with no
    ``workloads`` key and those that list the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_named(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py``, found by the name the
    data gives. ``kind`` is one of ``planes`` (``run``: one cell from
    bring-up to its result), ``patterns`` (``run``: a traffic pattern on
    its plane), ``dists`` (``quantile``: a length distribution),
    ``references`` (``score``) and ``layer_metrics`` (``read``)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        have = sorted(f[:-3] for f in os.listdir(os.path.join(BENCH_DIR, kind))
                      if f.endswith(".py"))
        raise Refused(f"no benchmark/{kind}/{name}.py; there are {have}")
    mod_name = f"benchmark_{kind}_" + re.sub(r"\W", "_", name)
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[mod_name]


def load_reader(metric_name: str):
    return load_named("layer_metrics", metric_name).read


def fold_seed(seed: int) -> int:
    """``jax.random.PRNGKey`` and the program's seeds are 32-bit signed;
    --seed may be larger. Distinct seeds below 2**31 - 1 stay distinct."""
    return int(seed) % (2**31 - 1)


class Device:
    """The chips this run may use; refuses anything but a TPU whose peaks
    are published in ``peaks.py``."""

    def __init__(self, chips: int, rehearse: bool):
        import jax

        from benchmark.lib import peaks

        devs = jax.devices()
        self.platform = devs[0].platform
        self.kind = devs[0].device_kind
        self.count = len(devs)
        self.rehearse = rehearse
        if rehearse:
            if self.platform != "cpu":
                raise Refused("a rehearsal runs on the CPU")
            self.peaks = None
        else:
            if self.platform != "tpu":
                raise Refused(f"no TPU: jax found platform "
                              f"{self.platform!r}")
            try:
                self.peaks = peaks.peaks(self.kind)
            except KeyError as exc:
                raise Refused(str(exc)) from exc
        if self.count < chips:
            raise Refused(f"the cell needs {chips} chips, jax found "
                          f"{self.count}")
        self.used = devs[:chips]

    def memory_peak_bytes(self) -> int:
        peak = 0
        for d in self.used:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak

    def as_dict(self) -> dict:
        return {"platform": self.platform, "kind": self.kind,
                "count": self.count,
                "memory_peak_bytes": self.memory_peak_bytes()}


class CompileCounter:
    """Counts, through ``jax.monitoring``: programs built (traced to
    MLIR); programs the backend made ready with the seconds that took (a
    compile, or a read from the persistent cache, which at these sizes is
    about a second a program); and persistent-cache misses (real
    compiles)."""

    def __init__(self):
        import jax

        self.traced = 0
        self.compiled = 0
        self.compile_s = 0.0
        self.cache_misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event == TRACE_EVENT:
            with self._lock:
                self.traced += 1
        elif event == COMPILE_EVENT:
            with self._lock:
                self.compiled += 1
                self.compile_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_MISS_EVENT:
            with self._lock:
                self.cache_misses += 1

    def snapshot(self) -> tuple[int, int, float, int]:
        with self._lock:
            return (self.traced, self.compiled, self.compile_s,
                    self.cache_misses)


def configure_jax_cache() -> str:
    """The program's own cache placement (``JAX_COMPILATION_CACHE_DIR`` if
    set, else ``<checkout>/.jax_cache``), with every program kept however
    quickly it compiled: a run after the first compiles nothing."""
    import jax

    from polyrl_tpu.utils.xla_cache import configure_compile_cache

    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def work_dir(cell_name: str) -> str:
    """A scratch directory of this cell inside the checkout, emptied."""
    path = os.path.join(WORK_DIR, cell_name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


class ProfilerWindow:
    """A ``jax.profiler`` trace of part of the measured window."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.out_dir)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def xplane_path(self) -> str:
        import glob

        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no xplane under {self.out_dir}")
        return found[-1]


def wait_until(pred, timeout_s: float, what: str, poll_s: float = 0.05):
    deadline = time.monotonic() + timeout_s
    while not pred():
        if time.monotonic() > deadline:
            raise TimeoutError(f"{what}: not within {timeout_s:.0f}s")
        time.sleep(poll_s)


def say(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def rehearsal(config: dict, mix: dict) -> tuple[dict, dict]:
    """The cell at a tiny size for a CPU walk-through: the sizes of
    ``configs/rehearsal.json`` with this configuration's architecture
    flags, and the mix with its ``rehearsal`` overrides applied."""
    tiny = load_config(os.path.join(BENCH_DIR, "configs", "rehearsal.json"))
    flags = ("qk_norm", "attention_bias", "tie_word_embeddings")
    tiny["config"].update({k: config["config"][k] for k in flags
                           if k in config["config"]})
    tiny["reference"] = config["reference"]
    small = dict(mix)
    over = dict(mix.get("rehearsal", {}))
    small["engine"] = {**mix["engine"], **over.pop("engine", {})}
    small.update(over)
    return tiny, small


def verdict(out: dict, rehearse: bool) -> bool:
    """``correct``: the reference agrees within the configuration's limits,
    every request succeeded, the kernels took their TPU paths, the engine
    never reset and, on the chip, the backend spent under
    ``MAX_COMPILE_S_IN_WINDOW`` compiling inside the window (a rehearsal's
    window is not a measurement). Programs first traced inside the window
    are counted (``programs_built``) whatever they cost: an eager
    ``jax.numpy`` call on a new shape is one, compiles in milliseconds and
    does not fail the run; a step program does."""
    c = out["checks"]
    ok = (c["reference"]["ok"] and c["kernels_ok"]
          and c["engine_recoveries"] == 0
          and out["failed"] == 0 and out["attempted"] > 0)
    if not rehearse:
        ok = ok and c["compile_seconds_in_window"] < MAX_COMPILE_S_IN_WINDOW
    return bool(ok)


def compared(out: dict, config: dict, rehearse: bool) -> dict:
    """Each number that ``verdict`` holds to a limit, beside that limit
    (all are "at most"): printed with every run, last on standard error
    and last in the result's line, so that a run that is not ``correct``
    says by how much."""
    c, limits = out["checks"], config["correct"]
    ref = c["reference"]
    rows = {
        "logprob_mean_abs_diff": (ref["logprob_mean_abs_diff"],
                                  limits["logprob_mean_abs_diff_max"]),
        "logprob_max_abs_diff": (ref["logprob_max_abs_diff"],
                                 limits["logprob_max_abs_diff_max"]),
        "requests_failed": (out["failed"], 0),
        "engine_recoveries": (c["engine_recoveries"], 0),
        "kernels_off_their_tpu_path": (0 if c["kernels_ok"] else 1, 0),
    }
    if not rehearse:
        rows["compile_seconds_in_window"] = (
            c["compile_seconds_in_window"], MAX_COMPILE_S_IN_WINDOW)
    return {k: {"value": v, "limit": lim} for k, (v, lim) in rows.items()}


def start_watchdog(limit_s: float) -> None:
    """A run that hangs must end itself and its manager, inside the first
    run's allowance: dump every thread's stack and exit 4, printing no
    result."""
    import faulthandler

    def watch() -> None:
        time.sleep(limit_s)
        print(f"[bench] still running after {limit_s:.0f}s; stacks:",
              file=sys.stderr, flush=True)
        faulthandler.dump_traceback(file=sys.stderr)
        for proc in CHILDREN:
            proc.kill()
        os._exit(4)

    threading.Thread(target=watch, name="bench-watchdog",
                     daemon=True).start()
