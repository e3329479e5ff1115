"""What every driver shares: the run's context, the device's description, the
profiler's start and stop, and the comparison records of ``correct``."""

from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

from . import trace as trace_lib


@dataclasses.dataclass
class RunContext:
    cell: Dict[str, Any]          # the workloads entry
    cfg: Dict[str, Any]           # the configuration as run (tiny laid over
                                  # it under --rehearse)
    traffic: Dict[str, Any]       # the traffic/job file, likewise
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float                # perf_counter at process start
    wall_start: float             # time.time() at process start
    out_dir: str

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])

    def log(self, **line) -> None:
        """An earlier line of the output: anything but the result."""
        print(json.dumps(line, default=float), flush=True)


@dataclasses.dataclass
class Compared:
    """One number of ``correct`` beside its limit."""
    name: str
    value: float
    limit: float
    exact: bool = False

    @property
    def ok(self) -> bool:
        if not math.isfinite(self.value):
            return False
        return self.value == self.limit if self.exact else \
            self.value <= self.limit

    def line(self) -> Dict[str, Any]:
        return dict(compared=self.name, value=self.value, limit=self.limit,
                    ok=self.ok)


@dataclasses.dataclass
class RunResult:
    compared: List[Compared]
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    records: Dict[str, Any]               # what the per-layer readers read
    memory_peak_bytes: int
    trace_path: Optional[str] = None

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(c.ok for c in self.compared)


def device_description() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


def memory_peak_bytes(devices=None) -> int:
    """Peak HBM held on the fullest chip: live buffers at their peak plus what
    the loaded programs reserve for their temporaries.  On a TPU the two are
    disjoint parts of the limit (PR 23's probe: 2.8 GB in use + 8.9 GB
    reserved + 5.3 GB free = the 16.9 GB limit), and a program's scratch is
    in the second, so ``peak_bytes_in_use`` alone would leave it out."""
    import jax
    best = 0
    for d in (devices or jax.local_devices()):
        st = d.memory_stats() or {}
        best = max(best, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return best


class Profiler:
    """``jax.profiler`` around a span, with the benchmark's own
    ``bench_window`` annotation inside it marking the window the metrics are
    taken over.  Python's tracer is off: only device events and runtime spans
    are wanted, and the trace stays small."""

    def __init__(self, out_dir: str, name: str):
        self.dir = os.path.join(out_dir, "trace-" + name)
        self._span = None
        self.started_at = self.stopped_at = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN)
        self._span.__enter__()
        self.started_at = time.perf_counter()

    def stop(self) -> str:
        import jax
        self.stopped_at = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return self.dir


def open_run(workload: str, seed: int, seconds: Optional[float] = None,
             trace: bool = False, rehearse: bool = False,
             t_start: Optional[float] = None,
             wall_start: Optional[float] = None):
    """What the command and the tools do before a driver runs: resolve the
    cell to its files, look for the chip (or, rehearsing, choose the CPU and
    lay the ``tiny`` blocks over the files), point jax's persistent cache into
    the checkout.  Returns ``(manifest, context, device description)``; no
    chip, no return."""
    from . import manifest as mf
    man = mf.Manifest()
    cell = man.cell(workload)
    cfg = mf.resolve_sizes(man.config(cell["config"]), rehearse)
    traffic = man.traffic(cell["traffic"])
    if rehearse:
        traffic = mf.deep_merge(traffic, traffic.get("tiny", {}))
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell["chips"] > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={cell['chips']}")
    try:
        dev = device_description()
    except RuntimeError as e:  # jax found no backend at all
        fail_no_chip(f"no accelerator: {e}")
    if not rehearse:
        if dev["platform"] != "tpu":
            fail_no_chip(f"platform is {dev['platform']!r}, not a TPU (use "
                         "--rehearse for a CPU rehearsal)")
        from . import program
        program.use_compile_cache()
    if dev["count"] < cell["chips"]:
        fail_no_chip(f"cell {cell['name']} asks for {cell['chips']} chips, "
                     f"jax sees {dev['count']}")
    out_dir = os.path.join(mf.BENCH_DIR, "out")
    os.makedirs(out_dir, exist_ok=True)
    ctx = RunContext(
        cell=cell, cfg=cfg, traffic=traffic, seed=seed,
        seconds=(float(man.data["run_seconds"]) if seconds is None
                 else float(seconds)),
        trace=trace, rehearse=rehearse,
        t_start=time.perf_counter() if t_start is None else t_start,
        wall_start=time.time() if wall_start is None else wall_start,
        out_dir=out_dir)
    return man, ctx, dev


def fail_no_chip(why: str) -> None:
    """No chip, no result."""
    print(f"benchmark: {why}", file=sys.stderr, flush=True)
    sys.exit(3)
