"""What every run shares: finding a cell's pieces by name, the device
check, and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
pieces are found from those names alone, under the benchmark's directory
(the first entry of ``paths``):

- the configuration: the file the ``configs`` entry names;
- the traffic mix: ``traffic/<traffic>.json``, whose ``kind`` names
- the driver: ``drivers/<kind>.py``, with ``run(cell, seed, seconds,
  trace, t_start)``;
- each per-layer metric: ``metrics/<metric>.py``, with
  ``read(trace, facts)`` returning a number or ``None``.

So a later cell, configuration, mix or metric is a new file and a new
entry, never an edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Dict, List

HERE = pathlib.Path(__file__).resolve().parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    root: pathlib.Path
    bench_dir: pathlib.Path
    name: str
    config_name: str
    traffic_name: str
    chips: int
    conf: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    def driver_path(self) -> pathlib.Path:
        return self.bench_dir / "drivers" / f"{self.kind}.py"

    def metric_path(self, name: str) -> pathlib.Path:
        return self.bench_dir / "metrics" / f"{name}.py"

    def run_dir(self) -> pathlib.Path:
        """Scratch space of this cell's runs inside the checkout (traces)."""
        return self.bench_dir / ".runs" / self.name


def load_module(path: pathlib.Path, prefix: str):
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    mod_name = prefix + "".join(c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """Resolve a cell of ``<root>/BENCHMARK.json`` to its files."""
    root = pathlib.Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench_dir = root / spec["paths"][0]
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    conf = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic_path = bench_dir / "traffic" / f"{w['traffic']}.json"
    traffic = json.loads(traffic_path.read_text())
    cell = Cell(root=root, bench_dir=bench_dir, name=name,
                config_name=w["config"], traffic_name=w["traffic"],
                chips=int(w["chips"]), conf=conf, traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])
    for path in [cell.driver_path()] + [cell.metric_path(m["name"])
                                        for m in cell.per_layer]:
        if not path.is_file():
            raise FileNotFoundError(f"{name}: missing {path}")
    return cell


def peaks(device_kind: str) -> Dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]


def devices(chips: int, require_chip: bool = True):
    """The first ``chips`` devices; with ``require_chip`` they must be
    TPUs."""
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: pathlib.Path) -> str:
    """The program's persistent compilation cache (a fixed directory in the
    checkout, or ``$JAX_COMPILATION_CACHE_DIR``), holding every program:
    only a cell's first run in a checkout compiles."""
    sys.path.insert(0, str(root / "src"))
    from repro.compile_cache import enable_compile_cache as enable

    where = enable()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def start_trace(path: pathlib.Path) -> None:
    """Start the profiler: device activity and host annotations, without
    the Python call tracer (it would stamp every Python call)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)


def model_config(conf: Dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registry's architecture with every size set from the file."""
    import dataclasses as dc

    from bench.reference import model_sizes
    from repro.configs import registry

    m = model_sizes(conf)
    base = registry.get(conf["arch"])
    cfg = dc.replace(base, num_layers=m["num_layers"], d_model=m["d_model"],
                     num_heads=m["num_heads"],
                     num_kv_heads=m["num_kv_heads"], head_dim=m["head_dim"],
                     d_ff=m["d_ff"], vocab_size=m["vocab_size"],
                     window=m.get("window"), rope_theta=m["rope_theta"],
                     norm_eps=m["norm_eps"],
                     tie_embeddings=m["tie_embeddings"],
                     param_dtype=m["param_dtype"])
    if cfg.head_dim_ != m["head_dim"]:
        raise ValueError(f"{conf['arch']}: head_dim {cfg.head_dim_} != "
                         f"{m['head_dim']}")
    return cfg


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs]
    return int(max(peaks_))


def free_device_memory() -> None:
    """Drop every array the run left on its devices, so that the reference
    starts on empty chips (what the check needs is on the host by then)."""
    import gc

    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()


def device_info(devs) -> Dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def percentile(xs: List[float], q: float) -> float:
    """The q-th percentile of every sample (linear between order
    statistics); ``nan`` for no samples."""
    import numpy as np

    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else float("nan")
