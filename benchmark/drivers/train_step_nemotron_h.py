"""Window of ``paddle.jit.compile_train_step`` steps of the one-part-a-layer
hybrid decoder (``configs/nemotron-3-nano-*``) on one chip.

As ``drivers/train_step_mellum2.py`` stands to ``drivers/train_step_moe.py``:
that driver's pieces are used unchanged where they fit (the program's feed
and call, the checked steps with the per-expert readings, the numbers
compared, the scopes' device seconds, the reseeding between calibration
seeds) and ``drivers/train_step.py``'s under them (the sync): ONE compiled
step with its state, driven from the seed through its first steps by the
window's own call and feed, then timed; the reference
(``lib/reference_nemotron_h.py``) follows the first three steps once the
window has closed and the program's state is freed. Nothing calls the step
after the window (``lib/program_spans.py`` relies on that). Each step's
routed load is kept, for the expert layers alone. What is this file's own:
the model, the paths it checks in the compiled step (the scopes of the
Mamba-2 mixer and of the expert layer, the scan's and the flash kernels, no
scan fallen to its ``jax.numpy`` chunks), the faults it plants, and two
more numbers compared: the first gradient's norm per B / C group of each
Mamba-2 input projection, and how the correction biases moved over the
checked steps under the balancing rule.
"""
import gc
import math
import time

import jax
import jax.numpy as jnp

from ..lib import compare, harness, reference_nemotron_h, traffic
from . import train_step_moe
from .train_step import (CHECKED_STEPS, IN_FLIGHT, SPANS, TRACE_SECONDS,
                         WARM_STEPS, sync)
from .train_step_moe import HYPER, _norm_per_expert, reseed, scope_seconds

# what the compiled step must hold at the cell's shapes: the scopes the
# per-layer metrics and the profiler's views read, the scan's two kernels
# and the flash kernels (no dense attention)
PATHS = ("ssd_scan", "short_conv", "gated_norm", "router", "experts",
         "shared_expert", "ssd_scan_fwd", "ssd_scan_bwd",
         "flash_attention_fwd", "flash_attention_bwd_dkv",
         "flash_attention_bwd_dq")
FAULTS = (("control_fp8", {"operands": reference_nemotron_h.fp8_operands}),
          *((f"fault_{name}", {"fault": name})
            for name in reference_nemotron_h.FAULTS),
          ("fault_half_batch", None),  # rows, from the cell's batch
          ("fault_state_unchanged", {"frozen": True}))


class Program(train_step_moe.Program):
    """The one compiled step with its state, and the window's call and feed
    (the interface ``train_step.checked_steps`` drives)."""

    def __init__(self, cell, setup):
        with setup.phase("import"):
            from ..lib import program_nemotron_h as prog
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
        self.sizes = sizes
        self.named = list(self.model.named_parameters())
        self.flat = [prog.flat_name(n) for n, _ in self.named]
        self.experts = [l.experts for l in self.model.model.layers
                        if l.kind == "experts"]
        self.expert_grad_norms = self.first_routed = None

    def first_moment_per_expert(self):
        """{'eu_w.1': norms}: Adam's first moment of each stacked expert
        leaf, one norm per held expert, and of each Mamba-2 input
        projection, one norm per B / C group in ``bc_group_norms``'s order
        (device arrays; read later, split by ``checked_steps``)."""
        moments = {flat: self.opt._accumulators[id(p)]["moment1"]
                   for (_, p), flat in zip(self.named, self.flat)}
        out = {flat: _norm_per_expert(m) for flat, m in moments.items()
               if flat.startswith(reference_nemotron_h.STACKED)}
        groups = reference_nemotron_h.bc_group_norms(
            {k: m for k, m in moments.items() if k.startswith("xbcz_w")},
            self.sizes)
        for flat in moments:
            if flat.startswith("xbcz_w"):
                out[flat] = jnp.stack([
                    groups[name] for name in
                    reference_nemotron_h.bc_group_names(flat, self.sizes)])
        return out

    def check_paths(self, on_chip):
        """The compiled step's temporary bytes, once every scan is seen to
        have taken its kernels and no attention to have fallen to the dense
        path, and (on the chip: a rehearsal's kernels run interpreted and
        carry no name) the compiled step to hold the scopes and the
        kernels. (The persistent cache has the executable: this is a
        fetch.)"""
        from paddle_tpu.profiler import trace

        fallen = ([e for e in trace.events(kind="ssd_chunks")
                   if e.attrs.get("path") != "vmem"]
                  + list(trace.events(kind="flash_fallback")))
        if fallen:
            raise RuntimeError(f"a mixer fell back: {fallen[0].attrs}")
        compiled = self.step._step.lower(*self.step._arg_specs).compile()
        if on_chip:
            text = compiled.as_text()
            for name in PATHS:
                if name not in text:
                    raise RuntimeError(f"the compiled step holds no {name}")
        return int(compiled.memory_analysis().temp_size_in_bytes)


def checked_steps(program, cell, ring, setup):
    """``train_step_moe.checked_steps``, and each correction bias's move over
    the checked steps (the balancing rule's, in the compiled step)."""
    got = train_step_moe.checked_steps(program, cell, ring, setup)
    per, got["bc_group_grad_norms"] = got["expert_grad_norms"], {}
    for leaf in program.flat:
        if leaf.startswith("xbcz_w"):
            for i, name in enumerate(reference_nemotron_h.bc_group_names(
                    leaf, cell.sizes)):
                got["bc_group_grad_norms"][name] = per.pop(f"{leaf}/{i}")
    start = program.prog.seeded.make(cell.sizes, cell.seed,
                                     cell.sizes["param_dtype"])
    got["bias_moves"] = reference_nemotron_h.bias_moves(
        {f"bias.{i}": b._value for i, b in
         program.prog.score_biases(program.model).items()}, start)
    return got


def numbers(got, ref, rate):
    """``train_step_moe.numbers``; ``bc_group_grad_norm_gap``: the widest
    gap of one B / C group's first-gradient norm in a Mamba-2 input
    projection (``reference_nemotron_h.bc_group_norms``); and
    ``bias_move_gap``: the share of the
    correction biases' elements whose move over the checked steps differs
    from the reference's by half a step of the balancing rule or more (an
    expert whose load sits by the mean can take the other sign on one
    side)."""
    out = train_step_moe.numbers(got, ref)
    out["bc_group_grad_norm_gap"] = compare.worst_leaf_gap(
        got["bc_group_grad_norms"], ref["bc_group_grad_norms"])
    moves, want = got["bias_moves"], ref["bias_moves"]
    differ = sum(int((abs(moves[k] - want[k]) >= rate / 2).sum())
                 for k in want)
    out["bias_move_gap"] = (differ / sum(a.size for a in want.values()), "")
    return out


def reference_readings(cell, ring, steps=CHECKED_STEPS, **kw):
    mix = cell.traffic
    return reference_nemotron_h.train(
        cell.sizes, cell.seed, ring[:steps], {k: mix[k] for k in HYPER},
        cell.sizes["param_dtype"], steps=steps, **kw)


def judge(cell, ring, got):
    """Run the reference and hold each number to its limit."""
    t_ref = time.perf_counter()
    ref = reference_readings(cell, ring)
    print(f"reference: {CHECKED_STEPS} steps in "
          f"{time.perf_counter() - t_ref:.1f}s", flush=True)
    checks = compare.Checks()
    rate = cell.sizes["router_bias_update_rate"]
    for name, (value, note) in numbers(got, ref, rate).items():
        if note:
            print(f"{name}: worst leaf {note}", flush=True)
        if name in cell.limits:
            checks.add(name, value, cell.limits[name])
        else:  # a number with no upper reading is printed, not compared
            print(f"read, not compared: {name} {value:.6g}", flush=True)
    return checks


def planted(cell):
    """[(name, the reference's keywords that plant it)]."""
    half = {"rows": slice(0, max(1, cell.traffic["batch"] // 2))}
    return [(name, half if kw is None else kw) for name, kw in FAULTS]


def calibrate(cell, seeds, n_controls):
    """Readings that the cell's limits are set from (``tools/calibrate.py``):
    one row per seed of the program's numbers against the reference and, for
    the first ``n_controls`` seeds, of the control (the reference with
    float8_e4m3 operands in the program's place) and of each planted fault.
    One process builds the step once; between seeds the weights are seeded
    anew and the optimizer's state zeroed. Each row also has the first
    step's routed slots by expert layer."""
    setup = harness.Setup()
    program = Program(cell, setup)
    got, routed = {}, {}
    for seed in seeds:
        ring = reseed(program, cell, seed)
        got[seed] = (ring, checked_steps(program, cell, ring, setup))
        routed[seed] = [int(a) for a in program.first_routed]
    program = None
    gc.collect()
    jax.clear_caches()

    def values(readings, ref):
        return {k: v for k, (v, _) in numbers(
            readings, ref, cell.sizes["router_bias_update_rate"]).items()}

    for i, seed in enumerate(seeds):
        cell.seed = seed
        ring, readings = got[seed]
        ref = reference_readings(cell, ring)
        row = {"seed": seed, "program": values(readings, ref),
               "first_step_routed_slots": routed[seed]}
        if i < n_controls:
            for name, kw in planted(cell):
                row[name] = values(reference_readings(cell, ring, **kw), ref)
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
    routed = [[int(a) for a in r] for r, _ in load]  # [step][expert layer]
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
        print(f"routed slots by step: {per_step}", flush=True)
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
