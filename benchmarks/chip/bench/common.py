"""What both drivers share: host spans, the compile counter, peak
memory, the profiler window and the comparison's bookkeeping."""
from __future__ import annotations

import contextlib
import logging
import os
import shutil
import tempfile

import numpy as np


def span(name: str):
    """A host span in the profiler's trace (free when nothing traces)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class CompileCounter(logging.Handler):
    """Counts the compilations JAX logs while it is attached."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.count = 0

    def emit(self, record):
        if "Finished XLA compilation" in record.getMessage():
            self.count += 1

    @contextlib.contextmanager
    def watching(self):
        import jax
        log = logging.getLogger("jax")
        prev = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        log.addHandler(self)
        try:
            yield self
        finally:
            log.removeHandler(self)
            jax.config.update("jax_log_compiles", prev)


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 on a backend
    that keeps no count, as the CPU of the tests)."""
    stats = [d.memory_stats() for d in devices]
    return max(int(s["peak_bytes_in_use"]) if s else 0 for s in stats)


class Profile:
    """The profiler over the measured window; the trace goes to a
    temporary directory that is removed once it has been read."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = None
        self.path = None

    def start(self):
        if not self.enabled:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(self.dir)

    def stop(self):
        if self.dir is None:            # not enabled, or never started
            return
        import jax
        jax.profiler.stop_trace()
        for dirpath, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    self.path = os.path.join(dirpath, f)
        if self.path is None:
            raise RuntimeError("the profiler wrote no .xplane.pb")

    def cleanup(self):
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)


def worst_leaf_gap(prog: list[dict], ref: list[dict],
                   keep=None) -> tuple[float, str]:
    """Largest |norm_prog - norm_ref| over leaves and nodes, each against
    the larger of that leaf's reference norm and the node's median leaf
    norm.  ``keep(node, name)`` leaves a leaf out where it is false."""
    worst, where = 0.0, ""
    for node, (p, r) in enumerate(zip(prog, ref)):
        med = float(np.median(list(r.values())))
        for name, rv in r.items():
            if keep is not None and not keep(node, name):
                continue
            gap = abs(p[name] - rv) / max(rv, med, 1e-30)
            if gap >= worst:
                worst, where = gap, f"node {node} {name}"
    return worst, where
