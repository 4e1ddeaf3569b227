"""Window of ``paddle.jit.compile_train_step`` steps of the sparse hybrid
decoder (``configs/qwen3-next-*``) on one chip.

As ``drivers/train_step.py``, whose pieces it uses unchanged where they fit
(the checked steps, the numbers compared, the sync, the norm and projection
helpers): ONE compiled step with its state, driven from the seed through its
first steps by the window's own call and feed, then timed; the reference
(``lib/reference_qwen3_next.py``) follows the first three steps once the
window has closed and the program's state is freed. Nothing calls the step
after the window (``lib/program_spans.py`` relies on that). Besides, each
step's routed load is kept: the expert layers' ``routed_slots`` and
``expert_rows`` buffers, read from the device after the window.
"""
import gc
import glob
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import compare, harness, reference_qwen3_next, traffic
from . import train_step
from .train_step import (CHECKED_STEPS, IN_FLIGHT, SPANS, TRACE_SECONDS,
                         WARM_STEPS, sync)

HYPER = ("lr", "b1", "b2", "eps", "wd")
KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dkv",
           "flash_attention_bwd_dq", "gated_delta_rule_fwd",
           "gated_delta_rule_bwd", "ragged-dot")
FAULTS = (("control_fp8", {"operands": reference_qwen3_next.fp8_operands}),
          ("fault_no_decay", {"fault": "no_decay"}),
          ("fault_capacity_drop", {"fault": "capacity_drop"}),
          ("fault_no_renorm", {"fault": "no_renorm"}),
          ("fault_half_batch", None),  # rows, from the cell's batch
          ("fault_state_unchanged", {"frozen": True}))


class Program:
    """The one compiled step with its state, and the window's call and feed
    (the interface ``train_step.checked_steps`` drives)."""

    def __init__(self, cell, setup):
        with setup.phase("import"):
            from ..lib import program_qwen3_next as prog
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

    def feed(self, ids):
        with harness.span("make_batch"):
            T = self.paddle.Tensor
            return (T(jnp.asarray(ids[:, :-1]), stop_gradient=True),
                    T(jnp.asarray(ids[:, 1:]), stop_gradient=True))

    def call(self, ids):
        if self.opt._step_count == 1 and self.expert_grad_norms is None:
            # before the second step moves Adam's first moment: the first
            # gradient's norm per held expert (a whole leaf's norm cannot
            # see one expert's slots go missing)
            self.expert_grad_norms = self.first_moment_per_expert()
            self.first_routed = self.routed_load()[0]
        x, y = self.feed(ids)
        with harness.span("train_step"):
            return self.step(x, y)

    def first_moment_per_expert(self):
        """{'egu_w.2/17': norm}: Adam's first moment of each stacked expert
        leaf, one norm per held expert (device arrays; read later)."""
        return {flat: _norm_per_expert(
            self.opt._accumulators[id(p)]["moment1"])
            for (_, p), flat in zip(self.named, self.flat)
            if flat.startswith(reference_qwen3_next.STACKED)}

    def in_leaf_order(self, made):
        return [made[name] for name in self.flat]

    def seeded_start(self, cell):
        return self.in_leaf_order(self.prog.seeded.make(
            cell.sizes, cell.seed, cell.sizes["param_dtype"]))

    def signs(self, cell):
        return self.in_leaf_order(self.prog.seeded.projection(cell.sizes))

    def routed_load(self):
        """The last step's (routed_slots, expert_rows) per expert layer, as
        device arrays: read after the window, so that no step waits."""
        return ([e.routed_slots._value for e in self.experts],
                [e.expert_rows._value for e in self.experts])

    def check_paths(self, on_chip):
        """The compiled step's temporary bytes, once it is seen (on the chip:
        a rehearsal's kernels run interpreted and carry no name) to hold the
        flash kernels, the delta rule's two kernels and the grouped products:
        no fallback to dense attention or to a capacity-bucketed dispatch at
        the cell's shapes. (The persistent cache has the executable: this is
        a fetch.)"""
        compiled = self.step._step.lower(*self.step._arg_specs).compile()
        if on_chip:
            text = compiled.as_text()
            for name in KERNELS:
                if name not in text:
                    raise RuntimeError(f"the compiled step holds no {name}")
        return int(compiled.memory_analysis().temp_size_in_bytes)


@jax.jit
def _norm_per_expert(a):
    a = a.astype(jnp.float32)
    return jnp.sqrt(jnp.square(a).sum(tuple(range(1, a.ndim))))


def checked_steps(program, cell, ring, setup):
    """``train_step.checked_steps`` (steps 1 to 3 through the window's own
    call and feed), with the first gradient's norm per held expert beside
    the per-leaf readings."""
    program.expert_grad_norms = None
    got = train_step.checked_steps(program, cell, ring, setup)
    share = float(jnp.asarray(1.0 - cell.traffic["b1"],
                              cell.sizes["param_dtype"]))
    got["expert_grad_norms"] = {
        f"{leaf}/{e}": float(v) / share
        for leaf, norms in program.expert_grad_norms.items()
        for e, v in enumerate(np.asarray(norms, np.float64))}
    return got


def numbers(got, ref):
    """``train_step.numbers``, and ``expert_grad_norm_gap``: the widest gap
    of one held expert's first-gradient norm, over the stacked leaves."""
    out = train_step.numbers(got, ref)
    out["expert_grad_norm_gap"] = compare.worst_leaf_gap(
        got["expert_grad_norms"], ref["expert_grad_norms"])
    return out


def scope_seconds(window):
    """{layer path: device seconds} over the traced stretch, forward, backward
    and recomputation summed, as ``Profiler.summary(layer_depth=3)`` books
    them (``layers.*/mixer/gated_delta_rule``): read with the program's own
    reader from the trace file before ``Window.reduce`` deletes it, because
    ``lib/xplane.py`` keeps op names and no scope. ``None`` untraced."""
    if window.profile is None:
        return None
    from paddle_tpu.profiler import statistic

    view = statistic.device_view(glob.glob(os.path.join(
        window.profile.dir, "plugins", "profile", "*", "*.xplane.pb")),
        layer_depth=3)
    return view and {path: sum(by_direction.values())
                     for path, by_direction in view["layers"].items()}


def reference_readings(cell, ring, steps=CHECKED_STEPS, **kw):
    mix = cell.traffic
    return reference_qwen3_next.train(
        cell.sizes, cell.seed, ring[:steps], {k: mix[k] for k in HYPER},
        cell.sizes["param_dtype"], steps=steps, **kw)


def judge(cell, ring, got):
    """Run the reference and hold each number to its limit."""
    t_ref = time.perf_counter()
    ref = reference_readings(cell, ring)
    print(f"reference: {CHECKED_STEPS} steps in "
          f"{time.perf_counter() - t_ref:.1f}s", flush=True)
    still = [{k for k, v in side["delta_norms"].items() if v == 0.0}
             for side in (got, ref)]
    print(f"leaves unmoved after {CHECKED_STEPS} steps: program "
          f"{len(still[0])}, reference {len(still[1])}, not the same ones: "
          f"{sorted(still[0] ^ still[1])}", flush=True)
    checks = compare.Checks()
    for name, (value, note) in numbers(got, ref).items():
        if note:
            print(f"{name}: worst leaf {note}", flush=True)
        if name in cell.limits:
            checks.add(name, value, cell.limits[name])
        else:  # a number with no upper reading is printed, not compared
            print(f"read, not compared: {name} {value:.6g}", flush=True)
    return checks


def reseed(program, cell, seed):
    """The built step at ``seed``'s start: the weights seeded anew, the
    optimizer's state zeroed. Returns the checked steps' batches."""
    cell.seed = seed
    sizes = cell.sizes
    program.prog.seed_weights(program.model, sizes, seed,
                              sizes["param_dtype"])
    program.opt._accumulators.clear()
    program.opt._step_count = 0
    program.step._opt_state = None
    return traffic.train_batches(cell.traffic, seed,
                                 sizes["vocab_size"])[:CHECKED_STEPS]


def calibrate(cell, seeds, n_controls):
    """Readings that the cell's limits are set from (``tools/calibrate.py``):
    one row per seed of the program's numbers against the reference and, for
    the first ``n_controls`` seeds, of the control (the reference with
    float8_e4m3 operands in the program's place) and of each planted fault.
    One process builds the step once; between seeds the weights are seeded
    anew and the optimizer's state zeroed."""
    setup = harness.Setup()
    program = Program(cell, setup)
    got = {}
    for seed in seeds:
        ring = reseed(program, cell, seed)
        got[seed] = (ring, checked_steps(program, cell, ring, setup))
    program = None
    gc.collect()
    jax.clear_caches()

    def values(readings, ref):
        return {k: v for k, (v, _) in numbers(readings, ref).items()}

    for i, seed in enumerate(seeds):
        cell.seed = seed
        ring, readings = got[seed]
        ref = reference_readings(cell, ring)
        row = {"seed": seed, "program": values(readings, ref)}
        if i < n_controls:
            for name, planted in FAULTS:
                if planted is None:
                    planted = {"rows": slice(
                        0, max(1, cell.traffic["batch"] // 2))}
                row[name] = values(
                    reference_readings(cell, ring, **planted), ref)
        yield row


FIRST_STEP = ("loss_gap_step1", "grad_norm_gap", "grad_sum_gap",
              "expert_grad_norm_gap")


def flipped_share(ours, theirs, held):
    """Per layer: (share of the k choices a token that are not among the
    other side's k; the same over the choices that fall on held experts
    [first, first + count), of either side's)."""
    first, count = held
    out = []
    for a, b in zip(ours, theirs):
        a, b = np.asarray(a), np.asarray(b)
        same = (a[:, :, None] == b[:, None, :]).any(-1)  # a's choice in b?
        on_a = (a >= first) & (a < first + count)
        on_b = (b >= first) & (b < first + count)
        back = (b[:, :, None] == a[:, None, :]).any(-1)
        lost = (on_a & ~same).sum() + (on_b & ~back).sum()
        out.append((float(1.0 - same.mean()),
                    float(lost / max(1, on_a.sum() + on_b.sum()))))
    return out


def routing(cell, seeds):
    """What of the cell's gaps is routing and what is rounding
    (``tools/routing.py``), one row per seed. ``flipped``: per layer, the
    share of the first step's expert choices that differ from the reference's
    own, for the program and for the float8 control. ``own`` / ``pinned``:
    the first step's numbers with each side routing for itself, and with the
    reference given the program's choice (the control: given the
    reference's), so that both sides of a comparison run the same experts on
    the same tokens."""
    setup = harness.Setup()
    program = Program(cell, setup)
    sizes = cell.sizes
    held = (sizes["held_first"], sizes["num_experts"])
    got = {}
    for seed in seeds:
        ring = reseed(program, cell, seed)
        chosen = [np.asarray(a) for a in program.prog.routed_experts(
            program.model, ring[0][:, :-1])]
        readings = checked_steps(program, cell, ring, setup)
        got[seed] = (ring, chosen, readings,
                     [int(a) for a in program.first_routed])
    program = None
    gc.collect()
    jax.clear_caches()

    def first_step(readings, ref):
        values = numbers(readings, ref)
        return {k: values[k][0] for k in FIRST_STEP}

    fp8 = {"operands": reference_qwen3_next.fp8_operands}
    for seed in seeds:
        cell.seed = seed
        ring, chosen, readings, compiled_held = got[seed]
        choice = {name: reference_qwen3_next.routed_experts(
            sizes, seed, ring[0][:, :-1], sizes["param_dtype"], **kw)
            for name, kw in (("reference", {}), ("control_fp8", fp8))}
        ref = reference_readings(cell, ring[:1], steps=1)
        yield {
            "seed": seed,
            # the eager walk against the compiled step's own counter
            "held_slots": {
                "eager": [int(((a >= held[0]) & (a < sum(held))).sum())
                          for a in chosen],
                "compiled_step": compiled_held},
            "flipped": {
                "program": flipped_share(chosen, choice["reference"], held),
                "control_fp8": flipped_share(choice["control_fp8"],
                                             choice["reference"], held)},
            "own": {
                "program": first_step(readings, ref),
                "control_fp8": first_step(reference_readings(
                    cell, ring[:1], steps=1, **fp8), ref)},
            "pinned": {
                "program": first_step(readings, reference_readings(
                    cell, ring[:1], steps=1,
                    pinned=[jnp.asarray(a) for a in chosen])),
                "control_fp8": first_step(reference_readings(
                    cell, ring[:1], steps=1, pinned=choice["reference"],
                    **fp8), ref)},
        }


def run(cell):
    setup = harness.Setup(cell.t_process)
    with setup.phase("device"):
        device = harness.device_record(cell.chips, cell.rehearse)
    counter = harness.CompileCounter()
    program = Program(cell, setup)
    step = program.step
    sizes, mix = cell.sizes, cell.traffic
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
            "scope_seconds": scopes,
        },
    }
