"""What every cell shares: the manifest, the files found by name, the
device check, the compile cache, the per-layer readers and the result
line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the configuration as run;
- ``traffic/<traffic>.json``: the mix, whose ``kind`` names the loop in
  ``kinds/<kind>.py``;
- ``metrics/<metric>.py``: the reader of one per-layer metric, a
  ``read(ctx)`` that returns a number, or None where it finds nothing.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# HF config.json keys -> the program's ModelConfig fields
CONFIG_KEYS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab", "sliding_window": "window",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
}


class BenchError(Exception):
    """A cell that cannot be run as asked; exits non-zero, no result."""


def manifest(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"{path} not found")
    return json.loads(path.read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise BenchError(f"no config {name!r} in BENCHMARK.json")


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"{path} not found")
    return json.loads(path.read_text())


def traffic_file(name: str, here: Path = HERE) -> Path:
    return here / "traffic" / f"{name}.json"


def kind_file(kind: str, here: Path = HERE) -> Path:
    return here / "kinds" / f"{kind}.py"


def metric_file(name: str, here: Path = HERE) -> Path:
    return here / "metrics" / f"{name}.py"


def load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_dims(conf: dict) -> dict:
    """The sizes the benchmark's own code (weights, reference, work
    counts) reads, from a configuration file."""
    hd = conf.get("head_dim") or conf["hidden_size"] // conf[
        "num_attention_heads"]
    return {
        "d_model": conf["hidden_size"], "d_ff": conf["intermediate_size"],
        "n_layers": conf["num_hidden_layers"],
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"], "head_dim": hd,
        "vocab": conf["vocab_size"], "window": conf.get("sliding_window"),
        "rope_theta": float(conf["rope_theta"]),
        "tie_embeddings": bool(conf.get("tie_word_embeddings", False)),
    }


def program_config(conf: dict):
    """The program's registered ModelConfig for ``conf["program_arch"]``,
    with the file's sizes and LUT-Q settings applied. A size the file
    states differently from the registry is applied, so the file is what
    runs."""
    from repro.configs import get_config
    from repro.core.spec import QuantSpec

    cfg = get_config(conf["program_arch"])
    kw = {CONFIG_KEYS[k]: conf[k] for k in CONFIG_KEYS if k in conf}
    q = conf["lutq"]
    kw["quant"] = QuantSpec(bits=q["bits"], constraint=q["constraint"],
                            kmeans_iters=q.get("kmeans_iters", 1))
    kw["act_bits"] = q["act_bits"]
    kw["kernel_backend"] = "auto"
    return cfg.replace(**kw)


def peaks_for(kind: str, here: Path = HERE) -> dict:
    table = load_json(here / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device_kind {kind!r} in peaks.json")
    return table[kind]


def setup_jax(chips: int, allow_cpu: bool = False):
    """Compile cache inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), then the device check."""
    import jax

    if not allow_cpu:  # tests on the CPU leave the process's cache alone
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
            ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if not allow_cpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise BenchError(f"need {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def device_info(devs, trace: dict = None) -> dict:
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]); inf counts as a value."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def read_metrics(names, ctx: dict, here: Path = HERE) -> dict:
    """Run each per-layer reader; a reader that finds nothing, or returns
    a number that is not finite, leaves its metric out."""
    out = {}
    for name, unit in names:
        mod = load_module(metric_file(name, here),
                          "chipbench_metric_" + name.replace(".", "_"))
        v = mod.read(ctx)
        if v is not None and math.isfinite(v):
            out[name] = {"value": float(v), "unit": unit}
    return out


def cell_metrics(bench: dict, workload: str, trace: bool):
    """(name, unit) of the metrics a cell reports in this kind of run."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [(m["name"], m["unit"]) for m in group
            if workload in m.get("workloads", [workload])]


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def emit(result: dict, checks: dict) -> None:
    """Checks on standard error last, then the result line last on
    standard output, with the checks as its last key."""
    for k, c in checks.items():
        print(f"[check] {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)

