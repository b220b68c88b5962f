"""HLO-derived ML collective traffic → NoC traffic matrices.

The paper's headline results are on "realistic workloads"; this module
closes the loop between the repo's model substrate and the NoC campaign
engine.  For one sharded model config it:

  1. lowers the phase programs (train step / fwd loss / decode step) under
     the mesh + sharding specs (``repro.sharding.specs``), exactly like
     ``repro.launch.dryrun`` but on the smoke config at a campaign-sized
     mesh;
  2. extracts every collective of the post-SPMD HLO — bytes, replica
     groups, ``source_target_pairs``, while-loop execution counts — via
     :func:`repro.analysis.hlo.collective_ops`;
  3. maps each collective onto logical-device (rank, rank) flows under the
     ring collective model (all-reduce rings, all-gather/reduce-scatter
     rings, all-to-all full exchange, collective-permute explicit pairs);
  4. embeds ranks onto a physical :class:`~repro.core.topology.Topology`
     (mesh axis k → torus dim k when the shapes line up, flat rank → node
     otherwise) and normalizes into a campaign traffic matrix.

The resulting :class:`MLWorkload` is a first-class ``CampaignSpec``
``workloads`` axis entry: it exposes ``.name`` and ``.matrix_for(topo)``
and flows through plan building, the plan cache, the certifier gate, and
the CSV/telemetry columns like any synthetic pattern.

Byte conservation is a tested invariant: per phase and per collective
kind, the (rank, rank) flow matrix sums exactly to the fabric wire bytes
reported by :func:`repro.analysis.hlo.collective_flow_totals`
(``tests/test_mltraffic.py``).

Deriving a workload needs ``jax.device_count() >= data*model``.  When the
current process was initialized with fewer host devices,
:func:`derive_workload` transparently re-derives in a subprocess with
``--xla_force_host_platform_device_count`` forced (the flag only takes
effect before jax's first init, and ``repro.noc`` imports jax at package
import — hence the child must receive it via the environment, not set it
itself).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import subprocess
import sys
import tempfile

import numpy as np

__all__ = ["WorkloadSpec", "MLWorkload", "collective_flows", "embed_ranks",
           "derive", "derive_workload", "DIRECT_PHASES"]

# phases lowered as real programs; "bwd" is derived as train − fwd
DIRECT_PHASES = ("fwd", "train", "decode")


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """One model workload to derive traffic for.

    ``data``/``model`` are the logical mesh shape
    (``repro.launch.mesh.make_mesh_for_devices``); ``axes`` is pure
    metadata naming those two mesh axes — the derivation never keys on
    the names, which is what makes the matrices invariant under mesh-axis
    relabeling (tested).  ``moe_pad_to`` pads the expert count so expert
    parallelism divides the model axis (e.g. qwen2-moe's 6 smoke experts
    → 8).  ``phases`` lists the programs to lower (subset of
    ``DIRECT_PHASES``).
    """

    arch: str
    data: int = 1
    model: int = 8
    batch: int = 4
    seq: int = 32
    decode_len: int = 32
    moe_pad_to: int = 0
    phases: tuple[str, ...] = ("train", "decode")
    axes: tuple[str, str] = ("data", "model")
    label: str = ""

    def __post_init__(self):
        bad = [p for p in self.phases if p not in DIRECT_PHASES]
        if bad:
            raise ValueError(f"unknown phases {bad}; derivable phases are "
                             f"{DIRECT_PHASES} ('bwd' is computed from "
                             f"train − fwd)")

    @property
    def num_devices(self) -> int:
        return self.data * self.model

    @property
    def name(self) -> str:
        return self.label or f"{self.arch}@{self.data}x{self.model}"

    def fingerprint(self) -> str:
        return hashlib.sha256(json.dumps(
            dataclasses.asdict(self), sort_keys=True,
            default=str).encode()).hexdigest()


def collective_flows(ops, num_devices: int) -> dict[str, np.ndarray]:
    """Per-kind (rank, rank) wire-byte matrices under the ring model.

    * all-reduce / all-gather / reduce-scatter: each group is a logical
      ring over its ranks in group order; every ring edge (i → next)
      carries the per-participant wire bytes (``2(g-1)/g·size`` for
      all-reduce, ``(g-1)/g·size`` otherwise).
    * all-to-all: every ordered pair within a group exchanges ``size/g``.
    * collective-permute: each ``source_target_pairs`` entry carries the
      full payload.

    Summing a kind's matrix reproduces that kind's
    :func:`repro.analysis.hlo.collective_flow_totals` entry exactly —
    the conservation invariant.
    """
    mats: dict[str, np.ndarray] = {}
    for op in ops:
        m = mats.setdefault(
            op.kind, np.zeros((num_devices, num_devices), np.float64))
        if op.kind == "collective-permute":
            for s, t in op.pairs:
                m[s, t] += op.count * op.size_bytes
            continue
        for grp in op.groups:
            g = len(grp)
            if g <= 1:
                continue
            if op.kind == "all-to-all":
                per = op.size_bytes / g
                for i in grp:
                    for j in grp:
                        if i != j:
                            m[i, j] += op.count * per
            else:
                factor = 2.0 if op.kind == "all-reduce" else 1.0
                per = factor * (g - 1) / g * op.size_bytes
                for a, b in zip(grp, grp[1:] + (grp[0],)):
                    m[a, b] += op.count * per
    return mats


def embed_ranks(topo, mesh_shape: tuple[int, ...]) -> np.ndarray:
    """Map logical mesh ranks onto physical topology node ids.

    Mesh rank r has mesh coordinates ``np.unravel_index(r, mesh_shape)``
    (last axis fastest — jax's device-array reshape order).  When the
    topology dims equal the mesh shape axis-for-axis (the
    ``repro.launch.mesh.ici_topology`` bridge), mesh axis k lands on
    torus dim k; ``Topology.node_id`` is dim-0-fastest, so this is NOT
    the identity for ``data > 1``.  Otherwise, if the node count covers
    the rank count, ranks map flat (rank r → node r) — e.g. an ``(1, 8)``
    mesh folded onto a 4×2 torus, where the model ring snakes across
    both physical dimensions.
    """
    d = int(np.prod(mesh_shape))
    if tuple(topo.dims) == tuple(mesh_shape):
        emb = np.empty(d, np.int64)
        for r in range(d):
            emb[r] = topo.node_id(np.unravel_index(r, mesh_shape))
        return emb
    if topo.num_nodes >= d:
        return np.arange(d, dtype=np.int64)
    raise ValueError(
        f"cannot embed {d} mesh ranks ({mesh_shape}) onto "
        f"{topo.name} ({topo.num_nodes} nodes)")


@dataclasses.dataclass
class MLWorkload:
    """Derived per-phase collective flows for one :class:`WorkloadSpec`.

    ``flows[phase][kind]`` is a (D, D) rank-pair wire-byte matrix;
    ``totals[phase][kind]`` is the HLO-side fabric byte total the matrix
    must sum to.  Phases present are exactly ``spec.phases``.
    """

    spec: WorkloadSpec
    flows: dict[str, dict[str, np.ndarray]]
    totals: dict[str, dict[str, float]]
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def name(self) -> str:
        return self.spec.name

    def phase_flows(self, phase: str) -> np.ndarray:
        """(D, D) byte matrix of one phase, summed over collective kinds.

        ``"bwd"`` is the derived backward residual ``max(train − fwd, 0)``
        (requires both in ``spec.phases``); ``"step"`` aliases ``"train"``.
        """
        if phase == "step":
            phase = "train"
        if phase == "bwd":
            return np.maximum(
                self.phase_flows("train") - self.phase_flows("fwd"), 0.0)
        if phase not in self.flows:
            raise KeyError(f"phase {phase!r} not derived for {self.name}; "
                           f"have {sorted(self.flows)}")
        d = self.spec.num_devices
        out = np.zeros((d, d), np.float64)
        for m in self.flows[phase].values():
            out += m
        return out

    def campaign_flows(self) -> np.ndarray:
        """The workload's campaign-axis byte matrix: all derived phases
        summed, except ``fwd`` whenever ``train`` is present (a train
        step re-runs the forward collectives — summing both would double
        count them)."""
        phases = [p for p in self.flows
                  if not (p == "fwd" and "train" in self.flows)]
        d = self.spec.num_devices
        out = np.zeros((d, d), np.float64)
        for p in phases:
            out += self.phase_flows(p)
        return out

    def matrix_for(self, topo) -> np.ndarray:
        """Campaign traffic matrix on ``topo``: rank flows embedded onto
        physical nodes, then normalized like every synthetic pattern
        (zero diagonal, Σ = 1) via ``traffic.from_pair_counts``."""
        from repro.core import traffic as traffic_mod
        flows = self.campaign_flows()
        if flows.sum() <= 0:
            raise ValueError(
                f"workload {self.name} derived zero collective bytes "
                f"(mesh {self.spec.data}x{self.spec.model}) — nothing to "
                f"route; use a sharded mesh (model > 1)")
        emb = embed_ranks(topo, (self.spec.data, self.spec.model))
        counts = np.zeros((topo.num_nodes, topo.num_nodes), np.float64)
        counts[np.ix_(emb, emb)] = flows
        return traffic_mod.from_pair_counts(topo, counts)

    # ----------------------------------------------------------------- #
    def save(self, path: str) -> None:
        arrs = {f"flow__{ph}__{k}": m
                for ph, kinds in self.flows.items()
                for k, m in kinds.items()}
        header = json.dumps({
            "spec": dataclasses.asdict(self.spec),
            "totals": self.totals,
            "meta": self.meta,
        })
        np.savez(path, __meta__=np.array(header), **arrs)

    @classmethod
    def load(cls, path: str) -> "MLWorkload":
        with np.load(path) as z:
            header = json.loads(str(z["__meta__"]))
            flows: dict[str, dict[str, np.ndarray]] = {}
            for key in z.files:
                if not key.startswith("flow__"):
                    continue
                _, ph, kind = key.split("__", 2)
                flows.setdefault(ph, {})[kind] = np.asarray(
                    z[key], np.float64)
        sd = header["spec"]
        for k in ("phases", "axes"):
            sd[k] = tuple(sd[k])
        return cls(spec=WorkloadSpec(**sd), flows=flows,
                   totals=header["totals"], meta=header.get("meta", {}))


# --------------------------------------------------------------------- #
# derivation: lower → extract → map
# --------------------------------------------------------------------- #
def _smoke_config(spec: WorkloadSpec):
    from repro.configs.base import get_arch
    cfg = get_arch(spec.arch).smoke
    if spec.moe_pad_to:
        cfg = cfg.replace(moe_pad_to=spec.moe_pad_to)
    return cfg


def _lower_phase(spec: WorkloadSpec, phase: str) -> str:
    """Compile one phase program under the spec's mesh + shardings and
    return its post-SPMD HLO text."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh_for_devices
    from repro.models import registry
    from repro.sharding import specs as sh

    cfg = _smoke_config(spec)
    mesh = make_mesh_for_devices(spec.data, spec.model)

    def sds(tree, spec_tree):
        return jax.tree.map(
            lambda s, p: jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=jax.sharding.NamedSharding(mesh, p)),
            tree, spec_tree,
            is_leaf=lambda x: isinstance(
                x, (jax.ShapeDtypeStruct, jax.sharding.PartitionSpec)))

    params_a = registry.abstract_params(cfg)
    pspecs = sh.param_specs(cfg, mesh, params_a)
    params_sds = sds(params_a, pspecs)
    b, s = spec.batch, spec.seq

    if phase in ("fwd", "train"):
        batch = {
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "mask": jax.ShapeDtypeStruct((b, s), jnp.float32),
        }
        if cfg.family == "vlm":
            batch["positions"] = jax.ShapeDtypeStruct((3, b, s), jnp.int32)
        if cfg.family == "encdec":
            batch["embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.enc_seq, cfg.d_model), cfg.jdtype)
        batch_sds = sds(batch, sh.batch_specs(mesh, batch))
        if phase == "fwd":
            from repro.train.train_step import loss_fn
            fn = lambda p, bt: loss_fn(cfg, p, bt)        # noqa: E731
            args = (params_sds, batch_sds)
        else:
            from repro.train.optimizer import OptConfig, init_opt_state
            from repro.train.train_step import make_train_step
            opt_cfg = OptConfig()
            opt_a = jax.eval_shape(lambda: init_opt_state(opt_cfg,
                                                          params_a))
            ospecs = sh.opt_specs(cfg, mesh, opt_a, pspecs)
            state_sds = {"params": params_sds, "opt": sds(opt_a, ospecs)}
            fn = make_train_step(cfg, opt_cfg, grad_accum=1)
            args = (state_sds, batch_sds)
    elif phase == "decode":
        mod = registry.model_module(cfg)
        cache_a = jax.eval_shape(
            lambda: registry.init_cache(cfg, b, spec.decode_len))
        cspecs = sh.cache_specs(cfg, mesh, cache_a, seq_parallel=False)
        cache_sds = sds(cache_a, cspecs)
        tokens = jax.ShapeDtypeStruct(
            (b, 1), jnp.int32,
            sharding=jax.sharding.NamedSharding(
                mesh, sh.fit_spec(mesh, (b, 1), (sh.DATA, None))))
        index = jax.ShapeDtypeStruct(
            (), jnp.int32,
            sharding=jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))

        def fn(params, tokens, cache, index):
            logits, cache = mod.decode_step(cfg, params, tokens, cache,
                                            index)
            return (jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32),
                    cache)

        args = (params_sds, tokens, cache_sds, index)
    else:
        raise ValueError(f"unknown phase {phase!r}")

    with mesh:
        compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def derive(spec: WorkloadSpec) -> MLWorkload:
    """Derive a workload in-process (needs ``jax.device_count() >=
    spec.num_devices``; see :func:`derive_workload` for the transparent
    subprocess fallback)."""
    import jax

    from repro.analysis.hlo import collective_flow_totals, collective_ops

    if jax.device_count() < spec.num_devices:
        raise RuntimeError(
            f"workload {spec.name} needs {spec.num_devices} devices, "
            f"process has {jax.device_count()} (set "
            f"--xla_force_host_platform_device_count before jax's first "
            f"init, or go through derive_workload)")
    flows: dict[str, dict[str, np.ndarray]] = {}
    totals: dict[str, dict[str, float]] = {}
    counts: dict[str, int] = {}
    for phase in spec.phases:
        text = _lower_phase(spec, phase)
        ops = collective_ops(text, spec.num_devices)
        flows[phase] = collective_flows(ops, spec.num_devices)
        totals[phase] = collective_flow_totals(ops)
        counts[phase] = len(ops)
    return MLWorkload(spec=spec, flows=flows, totals=totals,
                      meta={"collective_op_counts": counts})


def _derive_subprocess(spec: WorkloadSpec, timeout_s: float) -> MLWorkload:
    """Re-derive in a child interpreter with the host device count forced.

    The child's environment carries the XLA flag because ``repro.noc``
    (and thus this module's package) initializes jax at import — by the
    time a ``main()`` could set it, the device count is pinned.
    """
    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    # the child lowers on virtual host devices; on a chip machine the
    # parent already holds the accelerator, so the child must not try
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={spec.num_devices}")
    env["PYTHONPATH"] = os.pathsep.join(
        [src_root] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    with tempfile.TemporaryDirectory(prefix="mltraffic_") as tmp:
        out = os.path.join(tmp, "workload.npz")
        cmd = [sys.executable, "-m", "repro.noc.mltraffic",
               "--arch", spec.arch,
               "--data", str(spec.data), "--model", str(spec.model),
               "--batch", str(spec.batch), "--seq", str(spec.seq),
               "--decode-len", str(spec.decode_len),
               "--moe-pad-to", str(spec.moe_pad_to),
               "--phases", ",".join(spec.phases),
               "--label", spec.label,
               "--out", out]
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=timeout_s)
        if proc.returncode != 0:
            raise RuntimeError(
                f"subprocess derivation of {spec.name} failed "
                f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}")
        return MLWorkload.load(out)


def derive_workload(spec: WorkloadSpec, *, cache_dir: str | None = None,
                    timeout_s: float = 600.0) -> MLWorkload:
    """Derive a workload, in-process when the device count allows and via
    a subprocess otherwise; with ``cache_dir``, serve/store the derived
    npz by spec fingerprint (the bench stage points this at
    ``artifacts/bench/mltraffic`` so CI uploads the matrices)."""
    path = None
    if cache_dir:
        os.makedirs(cache_dir, exist_ok=True)
        stem = spec.name.replace("@", "_").replace("/", "-")
        path = os.path.join(
            cache_dir, f"{stem}__{spec.fingerprint()[:10]}.npz")
        if os.path.exists(path):
            return MLWorkload.load(path)
    import jax
    if jax.device_count() >= spec.num_devices:
        wl = derive(spec)
    else:
        wl = _derive_subprocess(spec, timeout_s)
    if path:
        wl.save(path)
    return wl


def main(argv=None) -> int:
    """Subprocess entry point: derive one workload, write it as npz."""
    import argparse

    ap = argparse.ArgumentParser(
        description="Derive HLO collective traffic for one model workload")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--decode-len", type=int, default=32)
    ap.add_argument("--moe-pad-to", type=int, default=0)
    ap.add_argument("--phases", default="train,decode")
    ap.add_argument("--label", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    spec = WorkloadSpec(
        arch=args.arch, data=args.data, model=args.model, batch=args.batch,
        seq=args.seq, decode_len=args.decode_len,
        moe_pad_to=args.moe_pad_to,
        phases=tuple(p for p in args.phases.split(",") if p),
        label=args.label)
    wl = derive(spec)
    wl.save(args.out)
    print(json.dumps({"workload": wl.name,
                      "phases": {p: sorted(t) for p, t in
                                 wl.totals.items()},
                      "total_bytes": float(wl.campaign_flows().sum())}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
