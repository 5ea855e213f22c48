"""Logical-axis sharding: MaxText-style rules mapping logical tensor axes to
physical mesh axes.

Every parameter / activation carries a tuple of logical axis names; a rule
table (chosen per mesh and workload) maps each name to a mesh axis (or None
for replication).  The production meshes are:

    single-pod : (data=16, model=16)            — 256 chips (one v5e pod)
    multi-pod  : (pod=2, data=16, model=16)     — 512 chips

The "pod" axis extends data parallelism across pods: batch and FSDP weight
shards span ("pod", "data") so the only cross-pod traffic is the gradient /
FSDP all-reduce family, which tolerates the thinner inter-pod links (DCN or
optical) — the standard multi-pod layout.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    table: Tuple[Tuple[str, Axis], ...]

    def as_dict(self) -> Dict[str, Axis]:
        return dict(self.table)

    def spec(self, logical_axes: Sequence[Optional[str]]) -> P:
        t = self.as_dict()
        out = []
        for name in logical_axes:
            if name is None:
                out.append(None)
            else:
                if name not in t:
                    raise KeyError(f"no sharding rule for logical axis "
                                   f"{name!r}")
                out.append(t[name])
        return P(*out)

    def sharding(self, mesh: Mesh,
                 logical_axes: Sequence[Optional[str]]) -> NamedSharding:
        return NamedSharding(mesh, self.spec(logical_axes))

    def shape_spec(self, mesh: Mesh, logical_axes, shape) -> P:
        """Divisibility-aware spec: a dimension whose size does not divide
        by its mesh-axis extent falls back to replication.  This happens for
        e.g. 3/8/9/24 (kv-)head counts against model=16; the resulting
        replicated compute is deliberate baseline behaviour and is surfaced
        by the roofline (HLO_FLOPs > MODEL_FLOPS)."""
        base = self.spec(logical_axes)
        out = []
        for dim, entry in zip(shape, tuple(base) + (None,) * len(shape)):
            if entry is None:
                out.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            # fall back to suffixes of a multi-axis spec before replicating:
            # e.g. fold_bh = 768 over (pod,data,model)=512 fails, but
            # (data,model)=256 divides — shard there, replicate over pod.
            chosen = None
            for start in range(len(axes)):
                cand = axes[start:]
                size = 1
                for a in cand:
                    size *= mesh.shape[a]
                if dim % size == 0:
                    chosen = cand if len(cand) > 1 else cand[0]
                    break
            out.append(chosen)
        return P(*out)

    def shape_sharding(self, mesh: Mesh, logical_axes,
                       shape) -> NamedSharding:
        return NamedSharding(mesh, self.shape_spec(mesh, logical_axes, shape))


def _filter(mesh_axes: Sequence[str], want: Sequence[str]) -> Axis:
    got = tuple(a for a in want if a in mesh_axes)
    if not got:
        return None
    return got if len(got) > 1 else got[0]


def make_rules(mesh: Mesh, *, seq_sharded: bool = False,
               fsdp: bool = True, moe_ep: bool = False,
               cache_seq_model: bool = False,
               seq_shard_acts: bool = False) -> ShardingRules:
    """Build the rule table for a mesh.

    seq_sharded — shard the sequence/cache axis over the data axes
                  (sequence parallelism; used for long_500k where batch=1).
    fsdp        — shard the parameter "embed" axis over data (ZeRO-3 style).
    moe_ep      — shard the expert axis over "model" (expert parallelism)
                  instead of sharding each expert's d_ff (tensor parallel).
    cache_seq_model — decode: shard the KV-cache sequence dim over "model"
                  (flash-decode layout; §Perf lever for collective-bound
                  decode with replicated GQA kv heads).
    """
    axes = mesh.axis_names
    data_axes = _filter(axes, ("pod", "data"))
    model = _filter(axes, ("model",))
    fsdp_axis = data_axes if fsdp else None
    all_axes = _filter(axes, ("pod", "data", "model"))

    cache_seq = model if cache_seq_model else \
        (data_axes if seq_sharded else None)
    table = (
        # --- activations ---
        ("batch", None if seq_sharded else data_axes),
        ("seq", data_axes if seq_sharded else None),
        # residual-stream sequence axis: Megatron-style sequence parallelism
        # over "model" when enabled (train §Perf lever); follows "seq"
        # otherwise.
        ("seq_res", model if seq_shard_acts else
         (data_axes if seq_sharded else None)),
        ("fold_bh", all_axes),
        ("act_embed", None),
        ("act_heads", model),
        ("act_kv_heads", model),
        ("act_mlp", model),
        ("act_vocab", model),
        ("act_experts", model if moe_ep else None),
        ("act_cap", None),
        ("cache_seq", cache_seq),
        ("cache_batch", None if seq_sharded else data_axes),
        ("ssm_heads_act", model),
        # --- parameters ---
        ("layers", None),
        ("embed", fsdp_axis),
        ("vocab", model),
        ("heads", model),
        ("kv_heads", model),
        ("mlp", model),
        ("experts", model if moe_ep else None),
        ("expert_mlp", None if moe_ep else model),
        ("ssm_inner", model),
        ("ssm_state", None),
        ("ssm_heads", model),
        ("conv", None),
        ("lora", None),
        ("img", None),
        ("norm", None),
    )
    return ShardingRules(table)


def tree_spec(rules: ShardingRules, axes_tree):
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(lambda ax: rules.spec(ax), axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and
                        all(isinstance(a, (str, type(None))) for a in x))


def tree_sharding(mesh: Mesh, rules: ShardingRules, axes_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        tree_spec(rules, axes_tree),
                        is_leaf=lambda x: isinstance(x, P))
