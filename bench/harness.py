"""What every load loop shares: spans, the compile counter, the tracer,
the program's collection type and the comparison with the reference.

A traffic file (``bench/traffic/<mix>.json``) names its ``loop`` and its
parameters. The loop is the module ``bench/loops/<loop>.py``, found by name, with

    run(cfg, mix, seed, seconds, tracer, compiles, control, log,
        on_window) -> dict

It builds the cell's data from the seed, warms every shape, calls
``on_window()`` (which returns the set-up seconds) just before the window,
measures for ``seconds``, and returns its record: ``setup_s``,
``attempted``, ``failed``, ``end_to_end`` (the cell's end-to-end metrics
but ``setup_s``), ``check`` (a function that compares the window's
answers with the reference after the window), and whatever fields its
per-layer readers take. Loops never print the result line.

Host spans, written into the profiler's trace when one is taken:
``bench.r_prep`` (building R or submitting requests), ``bench.join_call``,
``bench.serve_step`` and ``bench.wait_arrival``.
"""
from __future__ import annotations

import importlib.util
import os
import time

import numpy as np

from reference import Reference

now = time.perf_counter
PROFILE_KEYS = ("universe", "draw_mean_len", "max_len", "zipf_a",
                "len_sigma")


def load_module(bench_dir: str, kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``: a loop or a metric reader."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"run.py: no {kind} {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_loop(name: str, bench_dir: str):
    """The module ``bench/loops/<name>.py``."""
    return load_module(bench_dir, "loops", name)


def profile(cfg: dict) -> dict:
    return {k: cfg[k] for k in PROFILE_KEYS}


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Compiles:
    """Counts programs lowered (traced and compiled, or read from the
    persistent cache) while ``on`` is set, and keeps the longest pause of
    Python's garbage collector meanwhile: the two host stalls a window
    can meet."""

    KEY = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import gc

        import jax
        self.on, self.count, self.gc_pause_max_s = False, 0, 0.0
        self._gc_t0 = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        gc.callbacks.append(self._gc)

    def _event(self, key, _secs, **_kw):
        if self.on and key == self.KEY:
            self.count += 1

    def _gc(self, phase, _info):
        if phase == "start":
            self._gc_t0 = now()
        elif self.on:
            self.gc_pause_max_s = max(self.gc_pause_max_s,
                                      now() - self._gc_t0)


class Tracer:
    """Traces the start of the window: from ``start()`` to the first loop
    boundary ``seconds`` later."""

    def __init__(self, directory: str | None, seconds: float):
        self.directory, self.seconds = directory, seconds
        self.active, self.t0 = False, 0.0

    def start(self) -> None:
        if self.directory:
            import jax
            jax.profiler.start_trace(self.directory)
            self.active, self.t0 = True, now()

    def poll(self) -> float:
        """Stop once ``seconds`` have passed; returns the seconds that
        writing the trace took, for the loop to leave out of its clock."""
        if self.active and now() - self.t0 >= self.seconds:
            return self.stop()
        return 0.0

    def stop(self) -> float:
        if not self.active:
            return 0.0
        import jax
        t = now()
        jax.profiler.stop_trace()
        self.active = False
        return now() - t


def collection(c, universe: int):
    """The program's collection type over the CSR sets ``c``."""
    from repro.core.sets import SetCollection
    elems, offs = c
    sets = np.split(elems, offs[1:-1]) if len(offs) > 1 else []
    return SetCollection(sets, int(universe),
                         np.arange(len(sets), dtype=np.int32))


def check_answers(ref: Reference, asked: list, got: list,
                  control: bool) -> dict:
    """Compare the program's answers ``got[i]`` for the R sets ``asked[i]``
    with the reference (or, as the control, the reference's float32
    predicate in the program's place)."""
    missing = extra = ref_pairs = 0
    for r, answer in zip(asked, got):
        want = set(ref.matches(r).tolist())
        if control:
            answer = set(ref.matches(r, float32=True).tolist())
        ref_pairs += len(want)
        missing += len(want - answer)
        extra += len(answer - want)
    return {"checked_sets": len(asked), "reference_pairs": ref_pairs,
            "missing_pairs": missing, "extra_pairs": extra}
