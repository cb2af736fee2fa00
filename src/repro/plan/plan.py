"""ExecutionPlan: the single resolved description of one run.

A frozen, JSON-serialisable record of everything the entry points used to
assemble by hand: the mesh grid and its (C, R) refinement, the attention
scheme (`startrail` | `ulysses` | `ring`), the sequence layout, block
implementation knobs, the remat policy, and the microbatch count for
gradient accumulation. `launch.train`, `launch.dryrun`, the trainer and the
benchmarks all build their mesh + runtime from a plan — nothing else
hand-assembles `make_production_mesh` / `RunConfig.c` plumbing.

Construction paths:
  * `make_plan(cfg, shape, ...)` — explicit knobs, validated; unspecified
    knobs resolved by the analytical cost model (`repro.plan.cost`).
  * `repro.plan.autotune.autotune(...)` — measured refinement of the
    analytical top-k; persists the winner to `results/PLAN_<arch>_<shape>.json`.
  * `ExecutionPlan.load(path)` — reuse a persisted plan (`--plan` flag).
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Dict, List, Optional, Sequence

from repro.configs import registry
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.dist.meshes import PLACEMENTS
from repro.plan import cost

MESH_KINDS = ("local", "production")


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Fully-resolved run description. P_sp = n_devices / (pod * data)."""

    arch: str
    shape: str                     # shape name ('train_4k', 'smoke', ...)
    seq_len: int
    global_batch: int
    n_devices: int
    kind: str = "train"            # 'train' | 'prefill' | 'decode'
    data: int = 1
    pod: int = 1
    scheme: str = "startrail"      # 'startrail' | 'ring' | 'ulysses'
    c: int = 1
    placement: str = "team_inner"
    seq_scheme: str = "zigzag"
    block_impl: str = "ref"        # ring-step block kernel ('ref' | 'pallas')
    block_skip: bool = False
    remat: str = "attn_out"
    microbatches: int = 1
    sharding_rules: str = "default"
    grad_compression: str = "none"
    # 'local': the devices this process sees (TPU chips, or forced CPU host
    # devices); 'production': the 16x16 / 2x16x16 grid of launch.dryrun
    mesh_kind: str = "local"
    smoke: bool = False            # built on the registry's reduced config
    unroll_scans: bool = False
    # double-buffered ring scans (issue the next transfer before the block
    # kernel) + ring-transfer sub-chunking; see core/startrail.py
    pipeline_scan: bool = True
    comm_chunks: int = 1
    # ---- serving face (kind='decode' plans consumed by repro.engine) -----
    decode_batch: int = 0          # engine decode slots (0 = not a serve plan)
    page_size: int = 0             # KV page tokens (0 = not a serve plan)
    kernel_impl: str = "ref"       # paged-decode kernel ('ref' | 'pallas')
    replicas: int = 1              # gateway engine replicas (n_devices is
    #                                the per-replica device count)
    prefix_cache: bool = False     # block-hash prefix cache (repro.gateway)
    role: str = "unified"          # 'unified' | 'prefill' | 'decode' —
    #                                disaggregated serving (repro.gateway)
    host_tier_bytes: int = 0       # pinned-host KV tier capacity per engine
    #                                (0 = tier off; needs prefix_cache)

    # ---- derived sizes ---------------------------------------------------
    @property
    def dp_size(self) -> int:
        return self.pod * self.data

    @property
    def sp_size(self) -> int:
        return self.n_devices // self.dp_size

    @property
    def r(self) -> int:
        return self.sp_size // (self.c * self.c)

    def __post_init__(self):
        if self.mesh_kind not in MESH_KINDS:
            raise ValueError(f"mesh_kind must be one of {MESH_KINDS}")
        if self.scheme not in cost.SCHEMES:
            raise ValueError(f"scheme must be one of {cost.SCHEMES}, "
                             f"got {self.scheme!r}")
        if self.placement not in PLACEMENTS:
            raise ValueError(f"placement must be one of {PLACEMENTS}")
        if self.pod < 1 or self.data < 1 or self.n_devices < 1:
            raise ValueError("pod/data/n_devices must be positive")
        if self.n_devices % self.dp_size != 0:
            raise ValueError(
                f"n_devices={self.n_devices} not divisible by "
                f"pod*data={self.dp_size}")
        sp = self.sp_size
        if self.c < 1 or sp % (self.c * self.c) != 0:
            raise ValueError(
                f"C={self.c} invalid for P={sp}: need P % C^2 == 0")
        if self.scheme in ("ring", "ulysses") and self.c != 1:
            raise ValueError(f"scheme {self.scheme!r} implies C=1, "
                             f"got C={self.c}")
        if self.seq_len % sp != 0:
            raise ValueError(
                f"seq_len={self.seq_len} not divisible by SP={sp}")
        if self.seq_scheme == "zigzag" and self.seq_len % (2 * sp) != 0:
            raise ValueError(
                f"zigzag layout needs seq_len % (2*P) == 0, got "
                f"seq_len={self.seq_len}, P={sp}")
        if self.microbatches < 1:
            raise ValueError("microbatches must be >= 1")
        if self.comm_chunks < 1:
            raise ValueError("comm_chunks must be >= 1")
        s_team = self.c * self.seq_len // sp
        if self.comm_chunks > 1 and s_team % self.comm_chunks:
            raise ValueError(
                f"comm_chunks={self.comm_chunks} must divide the team "
                f"sequence length C*N/P = {s_team} (the axis the chunked "
                f"ring ppermute splits)")
        from repro.kernels.dispatch import IMPLS

        for knob, val in (("block_impl", self.block_impl),
                          ("kernel_impl", self.kernel_impl)):
            if val not in IMPLS:
                raise ValueError(f"{knob} must be one of {IMPLS}, "
                                 f"got {val!r}")
        if self.decode_batch < 0 or self.page_size < 0:
            raise ValueError("decode_batch/page_size must be >= 0")
        if self.page_size and self.seq_len % self.page_size:
            raise ValueError(
                f"seq_len={self.seq_len} not divisible by "
                f"page_size={self.page_size}")
        if self.replicas < 1:
            raise ValueError("replicas must be >= 1")
        if (self.replicas > 1 or self.prefix_cache) and not self.page_size:
            raise ValueError(
                "replicas/prefix_cache are serving-face knobs — only valid "
                "on kind='decode' plans with decode_batch/page_size set "
                "(build them with plan.make_serve_plan)")
        if self.role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role must be 'unified' | 'prefill' | 'decode', "
                f"got {self.role!r}")
        if self.role != "unified" and not self.page_size:
            raise ValueError(
                "role is a serving-face knob — only valid on plans with "
                "decode_batch/page_size set (plan.make_serve_plan)")
        if self.host_tier_bytes < 0:
            raise ValueError("host_tier_bytes must be >= 0")
        if self.host_tier_bytes and not self.prefix_cache:
            raise ValueError(
                "host_tier_bytes > 0 needs prefix_cache=True: the host "
                "tier is fed by prefix-cache eviction (spilled chains are "
                "rediscovered through the trie hash walk)")
        if self.kind == "train":
            if self.global_batch % self.dp_size != 0:
                raise ValueError(
                    f"global_batch={self.global_batch} not divisible by "
                    f"dp={self.dp_size}")
            b_local = self.global_batch // self.dp_size
            if b_local % self.microbatches != 0:
                raise ValueError(
                    f"per-device batch {b_local} not divisible by "
                    f"microbatches={self.microbatches}")

    # ---- the objects the rest of the system consumes ---------------------
    def shape_config(self) -> ShapeConfig:
        return ShapeConfig(self.shape, seq_len=self.seq_len,
                           global_batch=self.global_batch, kind=self.kind)

    def run_config(self) -> RunConfig:
        return RunConfig(
            c=self.c, seq_scheme=self.seq_scheme, block_impl=self.block_impl,
            kernel_impl=self.kernel_impl,
            block_skip=self.block_skip, multi_pod=self.pod > 1,
            remat=self.remat, grad_compression=self.grad_compression,
            sharding_rules=self.sharding_rules, unroll_scans=self.unroll_scans,
            attention_scheme=self.scheme, microbatches=self.microbatches,
            pipeline_scan=self.pipeline_scan, comm_chunks=self.comm_chunks)

    def build_mesh(self):
        """The refined `( [pod,] data, sp_grp, sp_ring, sp_team )` mesh."""
        from repro.dist import meshes

        if self.mesh_kind == "local":
            if self.pod != 1:
                raise ValueError("local meshes are single-pod")
            return meshes.local_mesh_for_tests(c=self.c, r=self.r,
                                               data=self.data)
        from repro.launch.mesh import make_production_mesh

        prod = make_production_mesh(multi_pod=self.pod > 1)
        return meshes.refine_mesh(prod, self.c, placement=self.placement)

    def build_train_step(self, model, adam_cfg, mesh=None):
        """(jitted_step, shardings) — see train.step.build_train_step."""
        from repro.train import step as train_step

        mesh = mesh if mesh is not None else self.build_mesh()
        return train_step.build_train_step(
            model, mesh, self.run_config(), self.shape_config(), adam_cfg)

    # ---- persistence -----------------------------------------------------
    def to_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d["r"] = self.r           # derived, recorded for readability
        d["sp_size"] = self.sp_size
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "ExecutionPlan":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    def save(self, path) -> pathlib.Path:
        path = pathlib.Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps({"plan": self.to_dict()}, indent=2))
        return path

    @classmethod
    def load(cls, path) -> "ExecutionPlan":
        d = json.loads(pathlib.Path(path).read_text())
        return cls.from_dict(d["plan"] if "plan" in d else d)


def plan_path(results_dir, arch: str, shape: str) -> pathlib.Path:
    return pathlib.Path(results_dir) / f"PLAN_{arch}_{shape}.json"


def make_plan(cfg: ModelConfig, shape: ShapeConfig, *, arch: Optional[str]
              = None, n_devices: int, data: int = 1, pod: int = 1,
              scheme: Optional[str] = None, c: Optional[int] = None,
              placement: Optional[str] = None,
              microbatches: Optional[int] = None,
              mesh_kind: str = "local", block_impl: Optional[str] = None,
              kernel_impl: Optional[str] = None,
              remat: str = "attn_out", sharding_rules: str = "default",
              grad_compression: str = "none", unroll_scans: bool = False,
              pipeline_scan: bool = True,
              comm_chunks: Optional[int] = None,
              overlap_frac: float = 1.0,
              cluster=None) -> ExecutionPlan:
    """Resolve one run into a validated ExecutionPlan.

    Knobs left as None are chosen by the analytical cost model
    (`cost.rank_arrangements`); explicitly-passed knobs are validated and
    illegal combinations raise (e.g. `scheme='ulysses'` when P > Hkv raises
    exactly as `core/ulysses.py` would at trace time). Unset
    `block_impl`/`kernel_impl` resolve per backend: the Pallas kernels on
    TPU, the jnp reference on CPU (`kernels.dispatch.resolve_impl`).
    Unset ``comm_chunks`` resolves via the overlap model
    (`cost.choose_comm_chunks`) at ``overlap_frac`` — pass the measured
    fraction from ``obs.commlog.overlap_report`` to stop the model
    assuming perfect comm/compute hiding.
    """
    from repro.kernels.dispatch import resolve_impl

    block_impl = resolve_impl(block_impl)
    kernel_impl = resolve_impl(kernel_impl)
    dp = pod * data
    if n_devices % dp != 0:
        raise ValueError(f"n_devices={n_devices} not divisible by "
                         f"pod*data={dp}")
    sp = n_devices // dp

    # sequence layout: causal load balance for training attention; SSM state
    # passing and serve-side cache layouts need contiguity
    if cfg.family in ("ssm", "hybrid") or shape.kind != "train":
        seq_scheme = "contiguous"
    else:
        seq_scheme = "zigzag"

    if scheme is not None:
        cost.check_scheme(cfg, sp, scheme)
    ranking = cost.rank_arrangements(cfg, shape, sp,
                                     batch=max(shape.global_batch // dp, 1),
                                     cluster=cluster)

    def matches(arr: cost.Arrangement) -> bool:
        if scheme is not None and arr.scheme != scheme:
            return False
        if c is not None and arr.c != c:
            return False
        if placement is not None and arr.c > 1 and arr.placement != placement:
            return False
        return True

    picked = next((e["arrangement"] for e in ranking if matches(e["arrangement"])),
                  None)
    if picked is None:
        legal = sorted({e["arrangement"].key for e in ranking})
        raise ValueError(
            f"no legal arrangement matches scheme={scheme!r} c={c} "
            f"placement={placement!r} at P={sp}; legal: {legal}")

    if microbatches is None:
        if mesh_kind == "production" and shape.kind == "train":
            microbatches = cost.choose_microbatches(
                cfg, shape, dp=dp, sp=sp, c=picked.c, remat=remat)
        else:
            microbatches = 1

    if comm_chunks is None:
        comm_chunks = cost.choose_comm_chunks(
            cfg, shape, sp, picked, batch=max(shape.global_batch // dp, 1),
            cluster=cluster, overlap_frac=overlap_frac)

    arch = arch or cfg.name
    return ExecutionPlan(
        arch=arch, shape=shape.name, seq_len=shape.seq_len,
        global_batch=shape.global_batch, n_devices=n_devices,
        kind=shape.kind, data=data, pod=pod, scheme=picked.scheme,
        c=picked.c,
        placement=picked.placement if picked.c > 1 else "team_inner",
        seq_scheme=seq_scheme, block_impl=block_impl,
        kernel_impl=kernel_impl,
        block_skip=cfg.window is not None and seq_scheme == "contiguous",
        remat=remat, microbatches=microbatches,
        sharding_rules=sharding_rules, grad_compression=grad_compression,
        mesh_kind=mesh_kind, smoke=registry.is_smoke(arch, cfg),
        unroll_scans=unroll_scans, pipeline_scan=pipeline_scan,
        comm_chunks=comm_chunks)


def make_serve_plan(cfg: ModelConfig, *, arch: Optional[str] = None,
                    n_devices: int, data: int = 1,
                    scheme: Optional[str] = None, c: Optional[int] = None,
                    placement: Optional[str] = None,
                    decode_batch: int = 4, page_size: int = 8,
                    max_len: int = 512, mesh_kind: str = "local",
                    kernel_impl: Optional[str] = None,
                    block_impl: Optional[str] = None,
                    sharding_rules: str = "default",
                    replicas: int = 1, prefix_cache: bool = False,
                    role: str = "unified", host_tier_bytes: int = 0,
                    cluster=None) -> ExecutionPlan:
    """Resolve one *serving* run (the engine's mesh + kernels) into a plan.

    ``kind='decode'``: ``seq_len`` is the engine capacity (``max_len``
    rounded up so both the SP degree and the page size divide it),
    ``global_batch``/``decode_batch`` the decode slot count, and
    ``kernel_impl`` the paged-decode kernel — backend-resolved when unset,
    like ``block_impl``. The arrangement (scheme, C, placement) comes from
    the same analytical ranking as training plans; for M=1 decode the ring
    degenerates to the lse-combine reduction, so the mesh factorisation
    mainly decides the *placement* of the cache shards.

    ``replicas``/``prefix_cache`` fill the gateway face (``repro.gateway``):
    ``n_devices`` is then the per-replica device count, and
    ``cost.prefix_cache_value`` prices the cache capacity against the
    hit-rate it can sustain (cached prefill tokens cost ~0 FLOPs — only
    page-table writes).
    """
    import math

    dp = data
    if n_devices % dp != 0:
        raise ValueError(f"n_devices={n_devices} not divisible by "
                         f"data={dp}")
    sp = n_devices // dp
    if page_size < 1:
        raise ValueError("page_size must be >= 1")
    if decode_batch < 1:
        raise ValueError("decode_batch must be >= 1")
    quantum = math.lcm(sp, page_size)
    seq_len = ((max_len + quantum - 1) // quantum) * quantum
    shape = ShapeConfig("serve", seq_len=seq_len, global_batch=decode_batch,
                        kind="decode")
    base = make_plan(cfg, shape, arch=arch, n_devices=n_devices, data=data,
                     scheme=scheme, c=c, placement=placement,
                     mesh_kind=mesh_kind, block_impl=block_impl,
                     kernel_impl=kernel_impl, sharding_rules=sharding_rules,
                     cluster=cluster)
    return dataclasses.replace(base, decode_batch=decode_batch,
                               page_size=page_size, replicas=replicas,
                               prefix_cache=prefix_cache, role=role,
                               host_tier_bytes=host_tier_bytes)


def make_role_plans(cfg: ModelConfig, *, roles: Sequence[str],
                    n_devices: int, **kw) -> List[ExecutionPlan]:
    """Per-replica plans for a disaggregated gateway.

    ``roles`` is one entry per replica (e.g. ``['prefill', 'decode']``);
    ``n_devices`` is the per-replica device count, as in the ``replicas``
    face of `make_serve_plan`. Every other knob is shared across roles so
    the engines stay numerically interchangeable — same kernels, same page
    size, same rounded capacity — which is what makes the prefill→decode
    KV handoff bit-exact. Returns one plan per role with ``replicas=1``
    (the gateway composes them; a mixed-role gateway cannot be described
    by a single plan's ``replicas`` count).
    """
    if not roles:
        raise ValueError("roles must name at least one replica")
    kw.pop("replicas", None)
    kw.pop("role", None)
    return [make_serve_plan(cfg, n_devices=n_devices, replicas=1,
                            role=role, **kw)
            for role in roles]
