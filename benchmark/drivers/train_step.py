"""Window of ``paddle.jit.compile_train_step`` steps on one chip.

Set-up builds ONE compiled step with its state, drives it from the seed
through its first steps by the window's own call and feed, and hands that same
object to the window. The reference follows the first three steps once the
window has closed and the program's state is freed.
"""
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import compare, harness, reference_gpt2, traffic

SPANS = ("make_batch", "train_step", "sync")
CHECKED_STEPS = 3
WARM_STEPS = 3
IN_FLIGHT = 2
TRACE_SECONDS = 4  # of the window's start, in a traced run


@jax.jit
def _norms_and_sums(leaves, signs):
    """Per leaf: its norm, and its entries summed under the fixed signs."""
    f32 = [a.astype(jnp.float32) for a in leaves]
    return (jnp.stack([jnp.sqrt(jnp.square(a).sum()) for a in f32]),
            jnp.stack([(a * s.astype(jnp.float32)).sum()
                       for a, s in zip(f32, signs)]))


@jax.jit
def _delta_norms_and_sums(new, old, signs):
    return _norms_and_sums([n.astype(jnp.float32) - o.astype(jnp.float32)
                            for n, o in zip(new, old)], signs)


class Program:
    """The one compiled step with its state, and the window's call and feed."""

    def __init__(self, cell, setup):
        with setup.phase("import"):
            from ..lib import program_gpt as prog
        self.prog, self.paddle = prog, prog.paddle
        paddle, sizes, mix = prog.paddle, cell.sizes, cell.traffic
        with setup.phase("model_on_device"):
            cfg, model = prog.build_model(sizes)
            self.model = paddle.amp.decorate(model, level="O2",
                                             dtype=sizes["param_dtype"])
            prog.seed_weights(self.model, sizes, cell.seed,
                              sizes["param_dtype"])
            from paddle_tpu.models import GPTPretrainingCriterion

            crit = GPTPretrainingCriterion(cfg)
            self.opt = paddle.optimizer.AdamW(
                learning_rate=mix["lr"], parameters=self.model.parameters(),
                weight_decay=mix["wd"], beta1=mix["b1"], beta2=mix["b2"],
                epsilon=mix["eps"])

            def loss_fn(logits, labels):
                return crit(logits.astype("float32"), labels)

            self.step = paddle.jit.compile_train_step(
                self.model, loss_fn, self.opt)
        self.named = list(self.model.named_parameters())
        self.flat = [self.prog.flat_name(n) for n, _ in self.named]

    def feed(self, ids):
        with harness.span("make_batch"):
            T = self.paddle.Tensor
            return (T(jnp.asarray(ids[:, :-1]), stop_gradient=True),
                    T(jnp.asarray(ids[:, 1:]), stop_gradient=True))

    def call(self, ids):
        x, y = self.feed(ids)
        with harness.span("train_step"):
            return self.step(x, y)

    def in_leaf_order(self, made):
        """A ``weights.per_layer`` dict as a list in the parameters' order."""
        out = []
        for n, _ in self.named:
            leaf, layer = self.prog.reference_leaf(n)
            out.append(made[leaf] if layer is None else made[leaf][layer])
        return out

    def seeded_start(self, cell):
        return self.in_leaf_order(self.prog.seeded.per_layer(
            cell.sizes, cell.seed, cell.sizes["param_dtype"]))

    def signs(self, cell):
        return self.in_leaf_order(
            self.prog.seeded.projection(cell.sizes, unstacked=True))


def sync(loss):
    with harness.span("sync"):
        loss._value.block_until_ready()


def checked_steps(program, cell, ring, setup):
    """Steps 1 to 3 through the window's own call and feed. Returns what is
    compared: each loss and, per leaf, the norm and the projection of the first
    gradient as the optimizer got it (from Adam's first moment after one
    step) and of the parameters' change after the three."""
    # Adam's first moment after one step is (1 - b1) * g, the factor rounded
    # to the type the state is held in
    share = float(jnp.asarray(1.0 - cell.traffic["b1"],
                              cell.sizes["param_dtype"]))
    with setup.phase("first_step_trace_compile_or_fetch"):
        first = [program.call(ring[0])]
        sync(first[0])
    with setup.phase("checked_steps"):
        moment1 = [program.opt._accumulators[id(p)]["moment1"]
                   for _, p in program.named]
        signs = program.signs(cell)
        grad_norms, grad_sums = (np.asarray(a, np.float64) / share
                                 for a in _norms_and_sums(moment1, signs))
        del moment1
        for i in range(1, CHECKED_STEPS):
            first.append(program.call(ring[i]))
        sync(first[-1])
        delta_norms, delta_sums = (np.asarray(a, np.float64) for a in
                                   _delta_norms_and_sums(
            [p._value for _, p in program.named], program.seeded_start(cell),
            signs))
        del signs

    def named(values):
        return dict(zip(program.flat, map(float, values)))

    return {"losses": [float(l) for l in first],
            "grad_norms": named(grad_norms), "grad_sums": named(grad_sums),
            "delta_norms": named(delta_norms), "delta_sums": named(delta_sums)}


def numbers(got, ref):
    """The numbers compared, in order: {name: (value, note)}. ``got`` is the
    program's readings, or a control's or a fault's put in its place."""
    out = {}
    for i, (g, want) in enumerate(zip(got["losses"], ref["losses"])):
        out[f"loss_gap_step{i + 1}"] = (abs(g - want) / abs(want), "")
    out["grad_norm_gap"] = compare.worst_leaf_gap(
        got["grad_norms"], ref["grad_norms"])
    out["grad_sum_gap"] = (compare.sum_gap_rms(
        got["grad_sums"], ref["grad_sums"], ref["grad_norms"]), "")
    idle = compare.idle_gradient_leaves(ref["grad_norms"])
    gap, leaf = compare.worst_leaf_gap(
        got["delta_norms"], ref["delta_norms"], skip=idle)
    out["delta_norm_gap"] = (gap, f"{leaf}; left out for an idle gradient: "
                             f"{sorted(idle)}")
    out["delta_sum_gap"] = (compare.sum_gap_rms(
        got["delta_sums"], ref["delta_sums"], ref["delta_norms"],
        skip=idle), "")
    return out


def reference_readings(cell, ring, **kw):
    mix = cell.traffic
    return reference_gpt2.train(
        cell.sizes, cell.seed, ring[:CHECKED_STEPS],
        {k: mix[k] for k in ("lr", "b1", "b2", "eps", "wd")},
        cell.sizes["param_dtype"], steps=CHECKED_STEPS, **kw)


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


def calibrate(cell, seeds, n_controls):
    """Readings that the cell's limits are set from (``tools/calibrate.py``):
    one row per seed of the program's numbers against the reference and, for
    the first ``n_controls`` seeds, of the control (the reference with
    float8_e4m3 operands in the program's place) and of the planted faults.
    One process builds the step once; between seeds the weights are seeded
    anew and the optimizer's state zeroed."""
    setup = harness.Setup()
    program = Program(cell, setup)
    sizes, mix = cell.sizes, cell.traffic
    got = {}
    for seed in seeds:
        cell.seed = seed
        program.prog.seed_weights(program.model, sizes, seed,
                                  sizes["param_dtype"])
        program.opt._accumulators.clear()
        program.opt._step_count = 0
        program.step._opt_state = None
        ring = traffic.train_batches(mix, seed, sizes["vocab_size"])
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
            for name, planted in (
                    ("control_fp8", {"operands": reference_gpt2.fp8_operands}),
                    ("fault_half_batch",
                     {"rows": slice(0, mix["batch"] // 2)}),
                    ("fault_state_unchanged", {"frozen": True})):
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
    losses, dispatch_s, raised = [], [], 0
    traced_steps = None
    window.open()
    while True:
        x, y = program.feed(ring[n_done % len(ring)])
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

    # -- after the window: memory, then free the program, then the reference
    values = [float(l) for l in losses]
    failed = raised + sum(not math.isfinite(v) for v in values)
    # peak_bytes_in_use leaves the executable's scratch out on this runtime:
    # the step's own memory_analysis() stands beside it (jit's cache has the
    # compiled step, so this compiles nothing)
    temp_bytes = 0 if raised else int(step._step.lower(
        *step._arg_specs).compile().memory_analysis().temp_size_in_bytes)
    print(f"memory: peak_bytes_in_use {peak_stat}, live at window start "
          f"{live_at_start}, compiled step temp {temp_bytes} "
          f"(memory_analysis)", flush=True)
    memory_peak = max(peak_stat, live_at_start + temp_bytes)
    reduced = window.reduce(SPANS)
    step = program = losses = loss = last = x = y = window = None
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
        },
    }
