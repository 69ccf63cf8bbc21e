"""What every cell shares: finding a cell's files by name, the device
check, the compile cache, the process clock, metric readers, and the
result line.

A cell (one ``workloads`` entry of ``BENCHMARK.json``) names a
configuration and a traffic mix. Its configuration file
(``bench/configs/<config>.json``) names the driver that runs it
(``kind`` -> ``bench/drivers/<kind>.py``) and its plain reference
(``reference`` -> ``bench/reference/<reference>.py``); its traffic mix is
``bench/traffic/<traffic>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``. Nothing here names a cell, a
configuration or a metric.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compilation cache: a fixed path inside the checkout
# (the path is part of the cache key, so it must never move)
CACHE_DIR = ROOT / ".bench_cache" / "jax"
# the TPU runtime's logs, kept in the checkout (its default is /tmp)
TPU_LOG_DIR = ROOT / ".bench_cache" / "tpu_logs"


class BenchError(SystemExit):
    """A run that cannot measure: exits non-zero and prints no result."""

    def __init__(self, msg: str):
        print(f"bench: {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]     # the BENCHMARK.json entries this cell reports
    per_layer: list[dict]


def _applies(metric: dict, cell: str, reported: set[str] | None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def load_cell(name: str, bench: dict | None = None) -> Cell:
    """Resolve a ``workloads`` entry and every file it names."""
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = entries[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / confs[w["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, None)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(name, w["chips"], config, traffic, e2e, per_layer)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    key = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = sys.modules[key] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's record
    (so interpreter start-up and imports count)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         "bench/peaks.json")
    return table[device_kind]


def keep_logs_in_checkout() -> None:
    """Point the TPU runtime's log directory into the checkout, unless
    the environment already names one. Call before JAX starts."""
    if "TPU_LOG_DIR" not in os.environ:
        TPU_LOG_DIR.mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(TPU_LOG_DIR)


def enable_compile_cache() -> None:
    """Every program this process compiles goes to, and comes from, one
    cache directory: the one ``JAX_COMPILATION_CACHE_DIR`` names where
    it is set, else the checkout's. The program reads that variable, so
    it takes the same directory."""
    import jax
    cache = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR)
    cache.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_tpu(chips: int):
    """The devices of this run: exactly ``chips`` TPU chips, else exit
    non-zero. There is no fallback to the CPU."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts the programs JAX lowers (a jit cache miss) while on."""

    def __init__(self):
        import jax
        self.on = False
        self.count = 0

        def listen(event, duration_secs, **kw):
            if self.on and event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps
    no such count: the CPU, in tests)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def read_per_layer(cell: Cell, ctx: dict) -> dict:
    """Each per-layer metric of the cell from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def emit(result: dict, checks: list[tuple[str, float, float]]) -> None:
    """Print the compared numbers beside their limits, last on stderr and
    last in the result line, then the result line as stdout's last."""
    for name, value, limit in checks:
        print(f"check {name}: {value!r} (limit {limit!r})",
              file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    print(json.dumps(result), flush=True)
