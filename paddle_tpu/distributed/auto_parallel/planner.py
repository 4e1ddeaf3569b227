"""Cost-model-driven sharding planner.

Reference analogue: python/paddle/distributed/auto_parallel/planner.py:826
(Planner driving an MCMC search over per-op dims_mappings, planner.py:379),
cost_model.py (comm+compute cost estimation over the op graph), cluster.py
(Device/Link/Machine capability model) and mapper.py (process→device
placement by link bandwidth).

TPU-native design: GSPMD already solves the reference's inner problem — given
a mesh and input/param shardings it propagates per-op partitionings and
inserts collectives — so the search space collapses from per-op dims_mapping
enumeration (the reference's PlanSpace, planner.py:105) to MESH
FACTORIZATIONS × ZeRO stage. An analytic roofline model scores each
candidate: MXU compute time (with small-tile efficiency decay), ICI/DCN
collective time (DP grad reduction, TP activation all-reduces, PP bubble,
ring-attention rotation), and HBM feasibility (params + optimizer state +
activations under remat). The mapper's job — keep the chattiest axis on the
fastest links — becomes axis ORDERING: mp innermost (intra-host ICI), dp
outermost (can ride DCN).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

__all__ = ["DeviceSpec", "ClusterSpec", "ModelDesc", "Candidate", "Plan",
           "CostModel", "Planner"]


@dataclass
class DeviceSpec:
    """One accelerator (reference: cluster.py Device — dp_gflops/memory).
    Defaults are TPU v5e's published peaks; ``mxu_efficiency`` is a builder's
    estimate, not a ledger number. ``plan()`` applies them to whatever
    ``jax.devices()`` returns — ROADMAP S0's peaks table (keyed by
    ``device_kind``, unknown kind an error) is to own these."""

    flops_bf16: float = 197e12          # peak MXU throughput, bytes/s
    mxu_efficiency: float = 0.68        # practical fraction at healthy tiles
    hbm_bytes: float = 16e9
    hbm_bw: float = 8.1e11              # bytes/s


@dataclass
class ClusterSpec:
    """reference: cluster.py Machine/Link graph. TPU pods are regular, so
    bandwidth per axis-neighbor is enough: ICI within a slice, DCN across
    hosts of a multi-slice job."""

    n_devices: int = 8
    devices_per_host: int = 8
    ici_bw: float = 9e10                # bytes/s per direction per link
    dcn_bw: float = 6.25e9              # bytes/s per host NIC
    coll_latency: float = 3e-6          # fixed cost per collective launch
    device: DeviceSpec = field(default_factory=DeviceSpec)

    def axis_bandwidth(self, inner: bool) -> float:
        """Collectives on inner (intra-host) axes ride ICI; outer axes may
        cross hosts (reference mapper.py places by link type)."""
        return self.ici_bw if inner else (
            self.ici_bw if self.n_devices <= self.devices_per_host
            else self.dcn_bw
        )


@dataclass
class ModelDesc:
    """What the cost model needs to know about the network — the TPU
    replacement for the reference's per-op graph walk (cost_model.py): for
    dense transformer-family models these five numbers determine FLOPs,
    comm volumes, and activation footprints to ~10%."""

    params: int                          # trainable parameter count
    layers: int                          # repeated blocks (pp split unit)
    hidden: int
    seq_len: int
    global_batch: int                    # sequences per optimizer step
    vocab: int = 0
    param_bytes: int = 4                 # master/weight dtype bytes
    act_bytes: int = 2                   # activation dtype (bf16 compute)
    opt_state_bytes_per_param: int = 8   # adam m+v fp32
    use_remat: bool = True

    @classmethod
    def from_gpt_config(cls, cfg, global_batch: int) -> "ModelDesc":
        h, L, v = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
        ffn = cfg.ffn_hidden_size or 4 * h
        params = L * (4 * h * h + 2 * h * ffn) + v * h + cfg.max_seq_len * h
        return cls(params=int(params), layers=L, hidden=h,
                   seq_len=cfg.max_seq_len, global_batch=global_batch, vocab=v)

    @classmethod
    def from_model(cls, model, seq_len: int, global_batch: int) -> "ModelDesc":
        """Introspect a generic Layer: parameter count from the tree, layer
        count from the longest repeated-sublayer container."""
        params = sum(
            int(math.prod(p.shape)) for p in model.parameters()
            if not p.stop_gradient
        )
        blocks = 1
        hidden = 0
        for _, sub in model.named_sublayers():
            kids = getattr(sub, "_sub_layers", {})
            same = {}
            for child in kids.values():
                same.setdefault(type(child).__name__, 0)
                same[type(child).__name__] += 1
            if same:
                blocks = max(blocks, max(same.values()))
        for p in model.parameters():
            if len(p.shape) == 2:
                hidden = max(hidden, min(int(p.shape[0]), int(p.shape[1])))
        return cls(params=params, layers=blocks, hidden=max(hidden, 1),
                   seq_len=seq_len, global_batch=global_batch)


@dataclass
class Candidate:
    dp: int = 1
    mp: int = 1
    pp: int = 1
    sep: int = 1
    zero_stage: int = 0
    micro_batches: int = 1

    @property
    def degrees(self) -> Dict[str, int]:
        return {"dp": self.dp, "mp": self.mp, "pp": self.pp, "sep": self.sep}

    def __str__(self):
        return (f"dp={self.dp} mp={self.mp} pp={self.pp} sep={self.sep} "
                f"zero={self.zero_stage} micro={self.micro_batches}")


@dataclass
class Plan:
    candidate: Candidate
    cost_ms: float
    breakdown: Dict[str, float]
    mem_bytes: float
    rejected: List[Tuple[Candidate, str]] = field(default_factory=list)

    def log(self) -> str:
        bd = " ".join(f"{k}={v:.2f}ms" for k, v in self.breakdown.items())
        return (f"[auto-parallel plan] {self.candidate} | est "
                f"{self.cost_ms:.2f} ms/step ({bd}) | "
                f"{self.mem_bytes / 1e9:.2f} GB/chip")


class CostModel:
    """Analytic roofline estimate of one training step under a candidate.

    Reference analogue: cost_model.py estimate_cost (graph-walk with static
    per-op tables + cross_node_penalty). Here the volumes come from the
    transformer structure and the times from the ClusterSpec's roofline.
    All-reduce time uses the ring bound 2·(n-1)/n · V / BW; reduce-scatter
    and all-gather are each half that.
    """

    def __init__(self, cluster: Optional[ClusterSpec] = None):
        self.cluster = cluster or ClusterSpec()

    # -- pieces --------------------------------------------------------------
    def _allreduce_ms(self, vol_bytes: float, n: int, bw: float,
                      n_launches: float = 1.0) -> float:
        """Ring bound + per-collective launch latency — the latency term is
        what makes fine-grained TP on small models lose to DP (bandwidth
        alone ties them)."""
        if n <= 1 or vol_bytes <= 0:
            return 0.0
        wire = 2.0 * (n - 1) / n * vol_bytes / bw
        return (wire + n_launches * self.cluster.coll_latency) * 1e3

    def _mxu_eff(self, c: Candidate, m: ModelDesc) -> float:
        """Small per-chip contractions can't fill the 128×128 systolic
        array: decay efficiency once hidden/mp (or ffn/mp) tiles drop below
        256 lanes."""
        base = self.cluster.device.mxu_efficiency
        tile = m.hidden / max(c.mp, 1)
        decay = min(1.0, tile / 256.0)
        # tiny per-chip batch also starves the array
        tok = m.global_batch * m.seq_len / (c.dp * c.sep * max(c.pp, 1))
        decay *= min(1.0, tok / 1024.0)
        # the floor only guards against divide-by-zero — it must stay far
        # below any real efficiency so tiny-model candidates still rank by
        # their relative decay instead of all saturating at the floor
        return max(base * decay, 1e-7)

    # -- main ----------------------------------------------------------------
    def estimate(self, m: ModelDesc, c: Candidate):
        """Return (cost_ms, breakdown, mem_bytes) or (None, reason, mem)."""
        cl = self.cluster
        n = c.dp * c.mp * c.pp * c.sep
        if n != cl.n_devices:
            return None, "degree product != device count", 0.0

        # ---- memory feasibility (reference: PlanFilter, planner.py:44) ----
        p_shard = m.params / (c.mp * c.pp)          # TP×PP split the weights
        zdiv = c.dp if c.zero_stage >= 1 else 1
        opt_bytes = m.params / (c.mp * c.pp) / zdiv * m.opt_state_bytes_per_param
        w_bytes = p_shard * m.param_bytes / (zdiv if c.zero_stage >= 3 else 1)
        g_bytes = p_shard * m.param_bytes / (zdiv if c.zero_stage >= 2 else 1)
        # activations: per layer ~ (10·h + attn) bytes/token without remat;
        # remat keeps ~2·h (block boundaries) and recomputes the rest
        tokens_local = (m.global_batch / c.dp) * (m.seq_len / c.sep) \
            / max(c.micro_batches if c.pp > 1 else 1, 1)
        act_per_layer = (2.0 if m.use_remat else 10.0) * m.hidden / c.mp \
            * m.act_bytes * tokens_local
        act_bytes = act_per_layer * (m.layers / c.pp) \
            * (min(c.pp, c.micro_batches) if c.pp > 1 else 1)
        mem = w_bytes + g_bytes + opt_bytes + act_bytes
        if mem > cl.device.hbm_bytes * 0.92:
            return None, f"needs {mem / 1e9:.1f} GB/chip", mem

        # ---- compute ------------------------------------------------------
        tokens = m.global_batch * m.seq_len
        flops = 6.0 * m.params * tokens              # fwd 2PT + bwd 4PT
        if m.use_remat:
            flops *= 4.0 / 3.0                       # recompute fwd once more
        eff = self._mxu_eff(c, m)
        compute_ms = flops / (n * cl.device.flops_bf16 * eff) * 1e3
        if c.pp > 1:
            mb = max(c.micro_batches, 1)
            bubble = (c.pp - 1) / (mb + c.pp - 1)
            compute_ms *= 1.0 / max(1.0 - bubble, 1e-6) - 0.0
        breakdown = {"compute": compute_ms}

        # ---- dp gradient reduction ---------------------------------------
        bw_dp = cl.axis_bandwidth(inner=False)
        grad_vol = m.params / (c.mp * c.pp) * m.param_bytes
        # ZeRO swaps all-reduce for reduce-scatter (+all-gather of updated
        # shards) — same ring volume, so the ring bound is identical. XLA
        # fuses the grad reduction into a handful of launches.
        breakdown["dp_grads"] = self._allreduce_ms(grad_vol, c.dp, bw_dp,
                                                   n_launches=2.0)

        # ---- tp activation all-reduces -----------------------------------
        bw_mp = cl.axis_bandwidth(inner=True)
        if c.mp > 1:
            act_vol = (m.global_batch / c.dp) * (m.seq_len / c.sep) \
                * m.hidden * m.act_bytes
            # 2 all-reduces fwd + 2 bwd per block (megatron pattern),
            # ×4/3 when remat replays the forward
            n_ar = m.layers * 4 * (4.0 / 3.0 if m.use_remat else 1.0)
            if c.pp > 1:
                n_ar /= c.pp  # per-chip layers only
            breakdown["tp_acts"] = self._allreduce_ms(
                act_vol * n_ar, c.mp, bw_mp, n_launches=n_ar
            )

        # ---- pp boundary p2p ---------------------------------------------
        if c.pp > 1:
            mb = max(c.micro_batches, 1)
            vol = (m.global_batch / c.dp) * m.seq_len / c.sep * m.hidden \
                * m.act_bytes / mb
            # each micro crosses pp-1 boundaries fwd + bwd
            n_hops = 2 * (c.pp - 1) * mb
            breakdown["pp_p2p"] = (
                n_hops * vol / bw_mp + n_hops * cl.coll_latency
            ) * 1e3

        # ---- ring attention rotation -------------------------------------
        if c.sep > 1:
            kv_vol = (m.global_batch / c.dp) * m.seq_len * m.hidden \
                / c.mp * m.act_bytes * 2  # k and v
            n_ring = m.layers / c.pp * (4.0 / 3.0 if m.use_remat else 1.0)
            breakdown["ring_kv"] = (
                (c.sep - 1) / c.sep * kv_vol * n_ring / bw_mp
                + n_ring * (c.sep - 1) * cl.coll_latency
            ) * 1e3

        total = sum(breakdown.values())
        return total, breakdown, mem


class Planner:
    """Enumerate mesh factorizations, score with the CostModel, pick argmin.

    Reference analogue: planner.py:826 (Planner.search over PlanSpace via
    MCMC). The TPU candidate space is small enough for exhaustive search.
    """

    def __init__(self, model_desc: ModelDesc,
                 cluster: Optional[ClusterSpec] = None,
                 long_context: bool = False, allow_pp: bool = True,
                 allow_mp: bool = True):
        self.model = model_desc
        self.cluster = cluster or ClusterSpec()
        self.cost_model = CostModel(self.cluster)
        self.long_context = long_context
        self.allow_pp = allow_pp
        self.allow_mp = allow_mp

    def candidates(self) -> List[Candidate]:
        n = self.cluster.n_devices
        m = self.model
        out = []
        for mp in _divisors(n) if self.allow_mp else [1]:
            for pp in _divisors(n // mp) if self.allow_pp else [1]:
                rest = n // (mp * pp)
                seps = [s for s in _divisors(rest)] if self.long_context else [1]
                for sep in seps:
                    dp = rest // sep
                    if pp > m.layers:
                        continue
                    if m.global_batch % (dp or 1):
                        continue
                    if sep > 1 and m.seq_len % sep:
                        continue
                    for zero in (0, 2, 3) if dp > 1 else (0,):
                        micro = max(2 * pp, 1) if pp > 1 else 1
                        # micro must divide the local batch
                        if pp > 1 and (m.global_batch // dp) % micro:
                            micro = math.gcd(m.global_batch // dp, micro)
                        out.append(Candidate(dp=dp, mp=mp, pp=pp, sep=sep,
                                             zero_stage=zero,
                                             micro_batches=micro))
        return out

    def plan(self, verbose: bool = False) -> Plan:
        return self.plan_topk(1, verbose=verbose)[0]

    def plan_topk(self, k: int, verbose: bool = False) -> List[Plan]:
        """The k cheapest feasible plans, best first — the candidate list a
        ProfileTuner can then MEASURE (reference: the planner hands its
        shortlist to the OptimizationTuner's trial loop)."""
        scored = []
        rejected: List[Tuple[Candidate, str]] = []
        for c in self.candidates():
            cost, breakdown, mem = self.cost_model.estimate(self.model, c)
            if cost is None:
                rejected.append((c, breakdown))
                continue
            # near-ties go to the simpler topology: every model-parallel
            # axis adds collectives the analytic model can underestimate
            cost *= 1.0 + 0.01 * (
                (c.mp > 1) + (c.pp > 1) + (c.sep > 1) + (c.zero_stage > 0)
            )
            scored.append((cost, c, breakdown, mem))
        if not scored:
            raise RuntimeError(
                "auto-parallel planner: no feasible candidate — model does "
                "not fit HBM at any factorization; add chips or shrink the "
                f"model (rejections: {rejected[:5]})"
            )
        scored.sort(key=lambda t: t[0])
        plans = [
            Plan(candidate=c, cost_ms=cost, breakdown=bd, mem_bytes=mem,
                 rejected=rejected)
            for cost, c, bd, mem in scored[:max(k, 1)]
        ]
        if verbose:
            for p in plans:
                print(p.log())
        return plans


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def plan_for_model(model, seq_len: int, global_batch: int,
                   cluster: Optional[ClusterSpec] = None,
                   allow_pp: Optional[bool] = None, topk: int = 1):
    """Shared auto-plan entry used by Engine(auto=True) and the fleet's
    strategy.auto path: introspect the model (TP-annotated weights gate mp;
    the pipeline-block protocol gates pp), build the ModelDesc, run the
    Planner, log the chosen spec. topk=1 returns the best Plan; topk>1
    returns the k cheapest Plans best-first (one introspection pass serves
    both the analytic choice and the profile tuner's shortlist)."""
    import jax

    desc = ModelDesc.from_model(model, seq_len=seq_len,
                                global_batch=global_batch)
    has_tp = any(
        getattr(p, "dist_spec", None) for p in model.parameters()
    ) or any(
        type(sub).__name__ in ("ColumnParallelLinear", "RowParallelLinear",
                               "VocabParallelEmbedding")
        for _, sub in model.named_sublayers()
    )
    has_pp = hasattr(model, "pp_blocks") if allow_pp is None else allow_pp
    cluster = cluster or ClusterSpec(n_devices=len(jax.devices()))
    plans = Planner(desc, cluster, allow_pp=has_pp,
                    allow_mp=has_tp).plan_topk(topk)
    print(plans[0].log())
    return plans[0] if topk == 1 else plans


def mesh_degrees_for(candidate: Candidate) -> Dict[str, int]:
    """Candidate → init_mesh degrees. ZeRO shards params/optimizer state
    over the mesh axis NAMED 'sharding' (parallel/sharding.py param_spec),
    so a zero_stage>0 plan moves its data-parallel degree onto that axis —
    otherwise the logged plan would claim ZeRO memory while the state stays
    replicated."""
    c = candidate
    if c.zero_stage > 0:
        return {"dp": 1, "mp": c.mp, "pp": c.pp, "sep": c.sep,
                "sharding": c.dp}
    return {"dp": c.dp, "mp": c.mp, "pp": c.pp, "sep": c.sep, "sharding": 1}
