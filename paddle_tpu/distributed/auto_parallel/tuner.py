"""Cluster description loading, process mapping, and the profile tuner.

Reference analogues:
- cluster.py: Cluster.build_from_file parsing a machines/devices/links
  JSON into a capability graph consumed by the cost model;
- mapper.py: mapping(dist_program, cluster) — place logical ranks onto
  physical devices so the chattiest communicators share the best links;
- tuner/: OptimizationTuner — try candidate strategies, MEASURE, keep the
  best (profile-guided, versus the planner's analytic model).

TPU-native: the capability graph collapses to ClusterSpec (regular pod
topologies); mapping collapses to axis ORDERING over jax.devices() (mp
innermost so TP collectives ride intra-host ICI); the tuner compiles and
times each candidate mesh on the real devices and keeps the fastest —
measurement beats any model when the hardware is in hand.
"""
from __future__ import annotations

import json
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .planner import Candidate, ClusterSpec, DeviceSpec

__all__ = ["cluster_from_json", "map_processes", "ProfileTuner"]


def cluster_from_json(path: str) -> ClusterSpec:
    """Parse the reference's cluster JSON (machines[].devices[] with
    gflops/memory, links[] with bandwidth) into a ClusterSpec.

    Unknown/missing fields fall back to the v5e defaults; heterogeneous
    clusters take the MINIMUM capability (the straggler sets the pace)."""
    with open(path) as f:
        doc = json.load(f)
    machines = doc.get("machines", [])
    if not machines:
        raise ValueError(f"{path}: no machines in cluster file")
    n_devices = 0
    per_host = []
    flops = []
    mem = []
    for m in machines:
        devs = [d for d in m.get("devices", [])
                if d.get("type", "GPU") not in ("CPU",)]
        per_host.append(len(devs))
        n_devices += len(devs)
        for d in devs:
            # reference stores double-precision gflops; sp_gflops when given
            g = d.get("sp_gflops") or d.get("dp_gflops")
            if g:
                flops.append(float(g) * 1e9)
            if d.get("memory"):
                mem.append(float(d["memory"]) * 1e9)
    intra = [float(l["bandwidth"]) * 1e9
             for l in doc.get("links", [])
             if l.get("type") in ("NVL", "PHB", "ICI")]
    inter = [float(l["bandwidth"]) * 1e9
             for l in doc.get("links", []) if l.get("type") == "NET"]
    dev = DeviceSpec()
    if flops:
        dev = DeviceSpec(flops_bf16=min(flops),
                         hbm_bytes=min(mem) if mem else DeviceSpec().hbm_bytes)
    return ClusterSpec(
        n_devices=n_devices,
        devices_per_host=max(per_host) if per_host else n_devices,
        ici_bw=min(intra) if intra else ClusterSpec().ici_bw,
        dcn_bw=min(inter) if inter else ClusterSpec().dcn_bw,
        device=dev,
    )


def map_processes(candidate: Candidate, devices=None):
    """Order physical devices for the candidate's mesh so the chattiest
    axis sits innermost (reference mapper.py places ranks by link
    bandwidth; on a pod the same goal is axis ordering: mp varies fastest
    over adjacent — intra-host — devices, dp slowest so it can cross
    DCN). Returns an ndarray shaped [pp, dp, sep, mp] of devices."""
    import jax

    devices = list(devices if devices is not None else jax.devices())
    c = candidate
    n = c.dp * c.mp * c.pp * c.sep
    if len(devices) < n:
        raise ValueError(f"candidate needs {n} devices, have {len(devices)}")
    arr = np.empty(n, dtype=object)
    arr[:] = devices[:n]
    # axis order outer->inner: pp, dp, sep, mp (mp adjacency first)
    return arr.reshape(c.pp, c.dp, c.sep, c.mp)


class TrialStateGuard:
    """Host-memory snapshot of model params/buffers + optimizer
    accumulators around profile trials (shared by Engine(tune=True) and
    the fleet auto path — the donation-safety logic must exist ONCE).

    Trial steps DONATE the device buffers and advance optimizer state, so
    device-array references die with the first trial; the snapshot lives
    in host numpy and `restore()` re-uploads it — call it before each
    candidate build and once more in a finally."""

    def __init__(self, model, optimizer):
        import jax as _jax
        import numpy as _np

        self._model = model
        self._opt = optimizer
        self._tensors = [
            (t, _np.asarray(_jax.device_get(t._value)))
            for t in list(model.parameters())
            + [b for _, b in model.named_buffers()]
        ]
        self._acc = {
            pid: {k: _np.asarray(_jax.device_get(v)) for k, v in st.items()}
            for pid, st in getattr(optimizer, "_accumulators", {}).items()
        }
        self._steps = getattr(optimizer, "_step_count", 0)

    def restore(self):
        import jax.numpy as _jnp

        for t, v in self._tensors:
            t._value = _jnp.asarray(v)
        if hasattr(self._opt, "_accumulators"):
            self._opt._accumulators = {
                pid: {k: _jnp.asarray(v) for k, v in st.items()}
                for pid, st in self._acc.items()
            }
            self._opt._step_count = self._steps


def calibration_scale(records, plans):
    """One-probe calibration shared by every measure-then-pick site:
    measured/estimated on the first candidate that both has an analytic
    cost and got measured. Returns (scale, log_line) or (None, None)."""
    measured = {r["candidate"]: r["ms"] for r in records if "ms" in r}
    probe = next(
        (p for p in plans if str(p.candidate) in measured
         and p.cost_ms > 0),
        None,
    )
    if probe is None:
        return None, None
    scale = measured[str(probe.candidate)] / probe.cost_ms
    line = (
        f"[auto-parallel tuner] calibration x{scale:.1f}: "
        + " ".join(f"{p.candidate}~{p.cost_ms * scale:.1f}ms"
                   for p in plans)
    )
    for p in plans:
        p.calibrated_ms = p.cost_ms * scale
    return scale, line


class ProfileTuner:
    """Measure candidate parallelization configs on the real devices and
    keep the fastest (reference: tuner/optimization_tuner.py's
    profile-based trial loop, minus the subprocess farm — one jit per
    candidate in-process)."""

    def __init__(self, model_fn, candidates: Sequence[Candidate],
                 warmup: int = 1, iters: int = 3, interleave: bool = False):
        """model_fn(candidate) -> (step_callable, example_batch_tuple);
        the callable must be ready to run (mesh installed, params placed).

        interleave=True: build every candidate first, then time them in
        round-robin rounds — ambient load drifting across the trial span
        hits all candidates equally instead of whichever ran during the
        bad minute. Requires each candidate to own its params (a SHARED
        model reshared per candidate would be re-placed on every
        cross-candidate call, biasing the timings — keep the sequential
        default there)."""
        self.model_fn = model_fn
        self.candidates = list(candidates)
        self.warmup = warmup
        self.iters = iters
        self.interleave = interleave
        self.records: List[Dict] = []
        self.best_step = None

    def tune(self, verbose: bool = False) -> Candidate:
        self.best_step = None  # the winner's ALREADY-COMPILED step object
        if self.interleave:
            return self._tune_interleaved(verbose)
        best = None  # (dt, cand, step) — losers are dropped immediately so
        # only one trial's executable + placed state is ever held alongside
        # the one being measured (a kept loser could OOM the next build)
        for cand in self.candidates:
            try:
                step, batch = self.model_fn(cand)
                for _ in range(max(self.warmup, 1)):
                    out = step(*batch)
                float(out)  # sync
                # min-of-iters: ambient load only ever slows an iteration,
                # so the minimum is the honest cost
                dt = float("inf")
                for _ in range(self.iters):
                    t0 = time.perf_counter()
                    out = step(*batch)
                    float(out)  # per-step sync (host read of the scalar loss)
                    dt = min(dt, time.perf_counter() - t0)
                self.records.append({"candidate": str(cand), "ms": dt * 1e3})
                if verbose:
                    print(f"[tuner] {cand}: {dt * 1e3:.2f} ms/step")
                if best is None or dt < best[0]:
                    best = (dt, cand, step)
            except Exception as e:  # infeasible candidate: record, move on
                self.records.append({"candidate": str(cand),
                                     "error": repr(e)})
                if verbose:
                    print(f"[tuner] {cand}: failed ({e})")
        if best is None:
            raise RuntimeError(
                f"profile tuner: every candidate failed: {self.records}"
            )
        self.best_step = best[2]
        return best[1]

    def _tune_interleaved(self, verbose: bool) -> Candidate:
        built = []  # [cand, step, batch, min_dt] — failed entries removed
        for cand in self.candidates:
            try:
                step, batch = self.model_fn(cand)
                for _ in range(max(self.warmup, 1)):
                    out = step(*batch)
                float(out)  # sync
                built.append([cand, step, batch, float("inf")])
            except Exception as e:
                self.records.append({"candidate": str(cand),
                                     "error": repr(e)})
                if verbose:
                    print(f"[tuner] {cand}: failed ({e})")
        for _ in range(self.iters):
            for entry in list(built):
                cand, step, batch, _dt = entry
                try:
                    t0 = time.perf_counter()
                    out = step(*batch)
                    float(out)
                    entry[3] = min(entry[3], time.perf_counter() - t0)
                except Exception as e:
                    # steady-state failure (late OOM, async XLA error):
                    # drop this candidate, keep the round-robin going
                    built.remove(entry)
                    self.records.append({"candidate": str(cand),
                                         "error": repr(e)})
                    if verbose:
                        print(f"[tuner] {cand}: failed ({e})")
        built = [e for e in built if e[3] < float("inf")]
        for cand, _s, _b, dt in built:
            self.records.append({"candidate": str(cand), "ms": dt * 1e3})
            if verbose:
                print(f"[tuner] {cand}: {dt * 1e3:.2f} ms/step")
        if not built:
            raise RuntimeError(
                f"profile tuner: every candidate failed: {self.records}"
            )
        best = min(built, key=lambda e: e[3])
        self.best_step = best[1]
        return best[0]
