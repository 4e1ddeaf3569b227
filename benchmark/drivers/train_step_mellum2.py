"""Window of ``paddle.jit.compile_train_step`` steps of the sliding-window
sparse decoder (``configs/mellum2-*``) on one chip.

As ``drivers/train_step_sdar.py`` stands to ``drivers/train_step_moe.py``:
that driver's pieces are used unchanged where they fit (the program's
interface, the checked steps with the per-expert readings, the numbers
compared, the scopes' device seconds) and ``drivers/train_step.py``'s under
them (the sync): ONE compiled step with its state, driven from the seed
through its first steps by the window's own call and feed, then timed; the
reference (``lib/reference_mellum2.py``) follows the first three steps once
the window has closed and the program's state is freed. Nothing calls the
step after the window (``lib/program_spans.py`` relies on that). Each step's
routed load is kept as there. What is this file's own: the model, the paths
it checks in the compiled step (the two attention scopes and the six flash
kernels: no dense attention), the ``flash_tiles`` events it keeps for the
tile share, the faults it plants.
"""
import gc
import math
import time

import jax

from ..lib import compare, harness, reference_mellum2, traffic
from . import train_step_moe
from .train_step import (CHECKED_STEPS, IN_FLIGHT, SPANS, TRACE_SECONDS,
                         WARM_STEPS, sync)
from .train_step_moe import HYPER, checked_steps, numbers, scope_seconds

# what the compiled step must hold at the cell's shapes: the scopes the
# per-layer metrics and the profiler's views read, and the flash kernels of
# both kinds of layer
PATHS = ("experts", "sliding_attention", "full_attention",
         "flash_attention_window_fwd", "flash_attention_window_bwd_dkv",
         "flash_attention_window_bwd_dq", "flash_attention_fwd",
         "flash_attention_bwd_dkv", "flash_attention_bwd_dq")
FAULTS = (("control_fp8", {"operands": reference_mellum2.fp8_operands}),
          *((f"fault_{name}", {"fault": name})
            for name in reference_mellum2.FAULTS),
          ("fault_half_batch", None),  # rows, from the cell's batch
          ("fault_state_unchanged", {"frozen": True}))


class Program(train_step_moe.Program):
    """The one compiled step with its state, and the window's call and feed
    (the interface ``train_step.checked_steps`` drives)."""

    def __init__(self, cell, setup):
        with setup.phase("import"):
            from ..lib import program_mellum2 as prog
        self.prog, self.paddle = prog, prog.paddle
        paddle, sizes, mix = prog.paddle, cell.sizes, cell.traffic
        with setup.phase("model_on_device"):
            _, model = prog.build_model(sizes)
            self.model = paddle.amp.decorate(model, level="O2",
                                             dtype=sizes["param_dtype"])
            prog.seed_weights(self.model, sizes, cell.seed,
                              sizes["param_dtype"])
            from paddle_tpu.models import GPTPretrainingCriterion

            crit = GPTPretrainingCriterion()
            self.opt = paddle.optimizer.AdamW(
                learning_rate=mix["lr"], parameters=self.model.parameters(),
                weight_decay=mix["wd"], beta1=mix["b1"], beta2=mix["b2"],
                epsilon=mix["eps"])

            def loss_fn(logits, labels):
                return crit(logits.astype("float32"), labels)

            self.step = paddle.jit.compile_train_step(
                self.model, loss_fn, self.opt)
        self.named = list(self.model.named_parameters())
        self.flat = [prog.flat_name(n) for n, _ in self.named]
        self.experts = [l.experts for l in self.model.model.layers]
        self.expert_grad_norms = self.first_routed = None

    def check_paths(self, on_chip):
        """The compiled step's temporary bytes, once it is seen (on the chip:
        a rehearsal's kernels run interpreted and carry no name) to hold the
        scopes and the flash kernels, and no attention to have fallen to the
        dense path. (The persistent cache has the executable: this is a
        fetch.)"""
        from paddle_tpu.profiler import trace

        fallen = trace.events(kind="flash_fallback")
        if fallen:
            raise RuntimeError(
                f"attention fell to the dense path: {fallen[0].attrs}")
        compiled = self.step._step.lower(*self.step._arg_specs).compile()
        if on_chip:
            text = compiled.as_text()
            for name in PATHS:
                if name not in text:
                    raise RuntimeError(f"the compiled step holds no {name}")
        return int(compiled.memory_analysis().temp_size_in_bytes)


def flash_tiles():
    """The ``flash_tiles`` events the program has left: one per trace of the
    attention (a layer's forward; the step is traced once)."""
    from paddle_tpu.profiler import trace

    return [dict(e.attrs) for e in trace.events(kind="flash_tiles")]


def reference_readings(cell, ring, steps=CHECKED_STEPS, **kw):
    mix = cell.traffic
    return reference_mellum2.train(
        cell.sizes, cell.seed, ring[:steps], {k: mix[k] for k in HYPER},
        cell.sizes["param_dtype"], steps=steps, **kw)


def judge(cell, ring, got):
    """Run the reference and hold each number to its limit."""
    t_ref = time.perf_counter()
    ref = reference_readings(cell, ring)
    print(f"reference: {CHECKED_STEPS} steps in "
          f"{time.perf_counter() - t_ref:.1f}s", flush=True)
    checks = compare.Checks()
    for name, (value, note) in numbers(got, ref).items():
        if note:
            print(f"{name}: worst leaf {note}", flush=True)
        if name in cell.limits:
            checks.add(name, value, cell.limits[name])
        else:  # a number with no upper reading is printed, not compared
            print(f"read, not compared: {name} {value:.6g}", flush=True)
    return checks


def calibrate(cell, seeds, n_controls):
    """Readings that the cell's limits are set from (``tools/calibrate.py``):
    one row per seed of the program's numbers against the reference and, for
    the first ``n_controls`` seeds, of the control (the reference with
    float8_e4m3 operands in the program's place) and of each planted fault.
    One process builds the step once; between seeds the weights are seeded
    anew and the optimizer's state zeroed (``train_step_moe.reseed``). Each
    row also has the first step's routed slots by layer."""
    program = Program(cell, harness.Setup())
    got, routed = {}, {}
    for seed in seeds:
        ring = train_step_moe.reseed(program, cell, seed)
        got[seed] = (ring, checked_steps(program, cell, ring,
                                         harness.Setup()))
        routed[seed] = [int(a) for a in program.first_routed]
    program = None
    gc.collect()
    jax.clear_caches()

    def values(readings, ref):
        return {k: v for k, (v, _) in numbers(readings, ref).items()}

    for i, seed in enumerate(seeds):
        cell.seed = seed
        ring, readings = got[seed]
        ref = reference_readings(cell, ring)
        row = {"seed": seed, "program": values(readings, ref),
               "first_step_routed_slots": routed[seed]}
        if i < n_controls:
            for name, planted in FAULTS:
                if planted is None:
                    planted = {"rows": slice(
                        0, max(1, cell.traffic["batch"] // 2))}
                row[name] = values(
                    reference_readings(cell, ring, **planted), ref)
        yield row


def run(cell):
    setup = harness.Setup(cell.t_process)
    with setup.phase("device"):
        device = harness.device_record(cell.chips, cell.rehearse)
    counter = harness.CompileCounter()
    program = Program(cell, setup)
    step = program.step
    sizes, mix = cell.sizes, cell.traffic
    with setup.phase("traffic"):
        ring = traffic.train_batches(mix, cell.seed, sizes["vocab_size"])
    tokens_per_step = mix["batch"] * mix["seq"]

    got = checked_steps(program, cell, ring, setup)
    with setup.phase("warm_up"):
        n_done = CHECKED_STEPS
        for _ in range(WARM_STEPS):
            last = program.call(ring[n_done % len(ring)])
            n_done += 1
        sync(last)
    live_at_start = harness.bytes_in_use()
    compiles_before = counter.compiles
    print(setup.line(), flush=True)
    setup_s = time.perf_counter() - cell.t_process

    # -- the window ----------------------------------------------------------
    window = harness.Window(cell, TRACE_SECONDS)
    losses, dispatch_s, load, raised = [], [], [], 0
    traced_steps = None
    window.open()
    while True:
        if n_done >= len(ring):
            raise RuntimeError(
                f"the ring of {len(ring)} batches is used up after "
                f"{len(losses)} steps of the window: a batch would repeat")
        x, y = program.feed(ring[n_done])
        td = time.perf_counter()
        try:
            with harness.span("train_step"):
                loss = step(x, y)
        except Exception as e:  # a step that raises fails; the state is gone
            print(f"step {n_done} raised {type(e).__name__}: {e}", flush=True)
            raised = 1
            break
        dispatch_s.append(time.perf_counter() - td)
        losses.append(loss)
        load.append(program.routed_load())
        n_done += 1
        if len(losses) > IN_FLIGHT:
            sync(losses[-1 - IN_FLIGHT])
        if window.trace_due():
            sync(losses[-1])
            traced_steps = len(losses)
            window.stop_trace()
        if window.over():
            break
    if losses:
        sync(losses[-1])
    window_s = window.close()
    if traced_steps is None:
        traced_steps = len(losses)
    compiles_in_window = counter.compiles - compiles_before
    peak_stat = harness.peak_bytes_in_use()

    # -- after the window: the load, memory, then free the program ----------
    values = [float(l) for l in losses]
    failed = raised + sum(not math.isfinite(v) for v in values)
    routed = [[int(a) for a in r] for r, _ in load]  # [step][layer]
    ran = [[int(a) for a in e] for _, e in load]
    # peak_bytes_in_use leaves the executable's scratch out on this runtime:
    # the step's own memory_analysis() stands beside it (jit's cache has the
    # compiled step, so this compiles nothing)
    temp_bytes = 0 if raised else program.check_paths(not cell.rehearse)
    print(f"memory: peak_bytes_in_use {peak_stat}, live at window start "
          f"{live_at_start}, compiled step temp {temp_bytes} "
          f"(memory_analysis)", flush=True)
    if routed:
        per_step = [sum(r) for r in routed]
        print(f"routed slots a step, all expert layers: min {min(per_step)} "
              f"max {max(per_step)} of {len(per_step)} steps; rows run "
              f"{min(map(sum, ran))}..{max(map(sum, ran))}", flush=True)
    memory_peak = max(peak_stat, live_at_start + temp_bytes)
    tiles = flash_tiles()
    scopes = scope_seconds(window)
    reduced = window.reduce(SPANS)
    step = program = losses = loss = last = x = y = window = load = None
    gc.collect()
    jax.clear_caches()

    checks = judge(cell, ring, got)
    if compiles_in_window:
        raise RuntimeError(
            f"{compiles_in_window} compilation(s) inside the measured window")
    return {
        "checks": checks, "attempted": len(values) + raised, "failed": failed,
        "setup_s": setup_s, "setup_split": setup.parts, "device": device,
        "memory_peak_bytes": memory_peak, "trace": reduced,
        "window": {
            "seconds": window_s, "steps": len(values),
            "tokens": len(values) * tokens_per_step,
            "tokens_per_step": tokens_per_step,
            "dispatch_s": dispatch_s, "traced_steps": traced_steps,
            "compiles": compiles_in_window,
            "first_losses": got["losses"], "last_loss": values[-1:],
            "routed_slots": routed, "expert_rows": ran,
            "scope_seconds": scopes, "flash_tiles": tiles,
        },
    }
