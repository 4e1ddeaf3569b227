"""Programs-per-step probe for the eager LeNet train step.

Measures the programs-per-step arithmetic: the number of device
programs one eager LeNet train step launches on the per-op path, the
lazy-dispatch path (FLAGS_eager_lazy_dispatch), and the whole-step
capture-and-replay path (FLAGS_eager_step_capture — one donated program per
step), using the dispatch counters exposed via paddle_tpu.profiler. Runs on
any backend; pin CPU with:

    JAX_PLATFORMS=cpu python tools/perf_eager_probe.py

Pattern modes (the PR 6 capture-coverage work):

    --grad-clip {global_norm,norm,value}   train with a built-in grad clip
    --accum-steps K                        K-microstep gradient accumulation

Both patterns must reach the captured tier in steady state — programs/step
1 on update steps, and each accumulate-only microstep one captured program.
With --check, the probe exits NONZERO when a steady-state loop still falls
back out of capture (any entry in capture_fallback_reasons, or a missing
replay), so it doubles as a CI perf-regression gate:

    python tools/perf_eager_probe.py --grad-clip global_norm --check
    python tools/perf_eager_probe.py --accum-steps 4 --check

Env knobs: PROBE_BATCH (default 16), PROBE_STEPS timed steps (default 5).
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.profiler as prof  # noqa: E402
from paddle_tpu.vision.models import LeNet  # noqa: E402

_CLIPS = {
    None: lambda: None,
    "global_norm": lambda: paddle.nn.ClipGradByGlobalNorm(1.0),
    "norm": lambda: paddle.nn.ClipGradByNorm(1.0),
    "value": lambda: paddle.nn.ClipGradByValue(0.1),
}


def build(bsz, clip=None, accum=1):
    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters(),
                                grad_clip=_CLIPS[clip]())
    loss_fn = paddle.nn.CrossEntropyLoss()
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((bsz, 1, 28, 28)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 10, (bsz,)))

    def cycle():
        # one optimizer step = `accum` microsteps (k-1 accumulate-only
        # backwards + the update step), the realistic large-batch pattern
        for _ in range(accum):
            loss = loss_fn(model(x), y)
            loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return cycle


def probe(lazy: bool, capture: bool, bsz: int, steps: int, clip, accum):
    paddle.set_flags({"FLAGS_eager_lazy_dispatch": lazy,
                      "FLAGS_eager_step_capture": capture})
    try:
        cycle = build(bsz, clip, accum)
        # warm-up: fill the per-op / segment compile caches; with capture on
        # this also arms the controller and compiles the captured step (the
        # synchronize joins FLAGS_eager_async_compile background builds so
        # the timed window replays finished executables)
        for _ in range(5):
            loss = cycle()
        paddle.device.synchronize()
        float(loss)

        prof.reset_dispatch_counters()
        t0 = time.time()
        for _ in range(steps):
            loss = cycle()
        float(loss)  # hard sync
        dt = time.time() - t0
        c = prof.dispatch_counters()
    finally:
        paddle.set_flags({"FLAGS_eager_lazy_dispatch": False,
                          "FLAGS_eager_step_capture": True})
    return c, dt


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--grad-clip", choices=sorted(k for k in _CLIPS if k),
                    default=None, help="train with a built-in gradient clip")
    ap.add_argument("--accum-steps", type=int, default=1, metavar="K",
                    help="K-microstep gradient accumulation (default 1)")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero when the steady-state captured loop "
                         "still falls back (CI perf-regression gate)")
    args = ap.parse_args()
    if args.accum_steps < 1:
        ap.error("--accum-steps must be >= 1")

    bsz = int(os.environ.get("PROBE_BATCH", 16))
    steps = int(os.environ.get("PROBE_STEPS", 5))
    k = args.accum_steps
    pattern = []
    if args.grad_clip:
        pattern.append(f"grad_clip={args.grad_clip}")
    if k > 1:
        pattern.append(f"accum_steps={k}")
    print(f"eager LeNet train step, batch {bsz}, {steps} steady-state "
          f"optimizer steps" + (f" [{', '.join(pattern)}]" if pattern else "")
          + "\n")

    gate_ok = True
    for mode, lazy, capture in (
        ("per-op", False, False),
        ("lazy", True, False),
        ("captured", True, True),
    ):
        c, dt = probe(lazy, capture, bsz, steps, args.grad_clip, k)
        per_step = c["programs"] / steps
        print(f"[{mode}] programs/step = {per_step:.1f}  "
              f"({steps / dt:.1f} steps/s)")
        print(f"    op={c['op_programs']} segment={c['segment_programs']} "
              f"backward={c['backward_programs']} "
              f"optimizer={c['optimizer_programs']} "
              f"captured={c['captured_programs']}")
        if lazy:
            print(f"    segments_flushed={c['segments_flushed']} "
                  f"cache hits/misses={c['segment_cache_hits']}/"
                  f"{c['segment_cache_misses']} "
                  f"flush_reasons={dict(c['flush_reasons'])}")
        if capture:
            print(f"    capture replays={c['capture_replays']} "
                  f"accum_replays={c['capture_accum_replays']} "
                  f"builds={c['capture_builds']} "
                  f"fallbacks={c['capture_fallbacks']} "
                  f"fallback_reasons={dict(c['capture_fallback_reasons'])}")
            # steady-state contract: every update step replayed captured
            # (programs = 1 update + k-1 accumulate microsteps per cycle)
            # and the fallback histogram stayed empty
            expect = steps * k
            ok = (
                c["capture_fallbacks"] == 0
                and c["capture_replays"] >= steps
                and c["capture_accum_replays"] >= steps * (k - 1)
                and c["captured_programs"] == expect
                and c["programs"] == expect
            )
            gate_ok = gate_ok and ok
            print(f"    steady-state capture: {'OK' if ok else 'FELL BACK'} "
                  f"(expected {expect} captured programs, got "
                  f"{c['captured_programs']})")
        print()

    if not gate_ok:
        print("FAIL: steady-state loop fell back out of whole-step capture",
              file=sys.stderr)
        return 2 if args.check else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
