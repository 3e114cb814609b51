"""Logical-axis sharding rules, divisibility-aware.

Every parameter and activation in the model zoo is annotated with *logical*
dim names (e.g. ("vocab", "embed"), ("batch", "seq", "embed")).  A
``Sharder`` resolves logical names to mesh axes through a rule table, with
two safety valves that make one rule set work across all ten architectures
and a fixed 16×16 (or 2×16×16) mesh:

  * divisibility — a dim is only sharded if its size divides evenly by the
    mesh axis size; otherwise it silently falls back to replicated (e.g.
    whisper-tiny's 6 heads on a model=16 axis).
  * profile — "tp" (Megatron tensor parallelism: heads/d_ff/vocab/experts on
    the model axis) or "sp" (sequence parallelism: activations seq-sharded
    on the model axis; used for head counts that cannot shard, per-arch in
    configs).

Batch always shards over ("pod","data") (multi-pod) or ("data",); decode
caches shard their sequence dim over the model axis (flash-decoding-style
partial softmax, SPMD inserts the combine collectives).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Logical dim -> candidate mesh axes, tried in order; first divisible wins.
_TP_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod+data",),     # composite: shards over pod AND data
    "tokens": ("pod+data",),    # flattened batch*seq (loss chunks)
    "seq": (),                  # replicated in tp profile (per-device full seq)
    "kv_seq": ("model",),       # decode cache: sequence-sharded (flash-decode)
    "embed": (),
    "heads": ("model",),
    "kv_heads": (),             # kv replicated; q heads carry the TP
    "q_per_kv": (),
    "head_dim": (),
    "dff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "moe_groups": ("pod+data",),
    "expert_dff": (),
    "ssm_heads": ("model",),
    "ssm_headdim": (),
    "ssm_state": (),
    "conv_kernel": (),
    "conv_channels": ("model",),
    "groups": (),
    "enc_seq": (),
    "patches": (),
    "stage": ("pod",),          # pipeline stages ride the pod axis if used
    # Solver-family (learned-stencil) params: the tap dim is tiny (2*ndim),
    # so it replicates; grid rows may shard over data, columns/depth stay
    # local so each shard holds contiguous stencil rows.
    "taps": (),
    "grid_row": ("data",),
    "grid_col": (),
    "grid_depth": (),
}

_SP_RULES: dict[str, tuple[str, ...]] = dict(
    _TP_RULES,
    seq=("model",),
    tokens=("pod+data+model", "pod+data"),
    heads=(),
    dff=(),
    conv_channels=(),
    ssm_heads=(),
    # ZeRO-3-style: weights shard over data on their embed dim and are
    # all-gathered at use (activations' embed dim stays unsharded because
    # batch claims the data axis first — one axis is used at most once).
    embed=("data",),
    # vocab stays model-sharded: the lm_head matmul contracts embed (local)
    # and the xent reduction over vocab psums over the model axis.
)

PROFILES = {"tp": _TP_RULES, "sp": _SP_RULES}


@dataclasses.dataclass(frozen=True)
class Sharder:
    mesh: Mesh
    profile: str = "tp"
    # long_500k / batch=1 decode: batch cannot shard, so spread cache state
    # over the data axis instead (ssm head-dim / kv seq).
    state_over_data: bool = False

    def __post_init__(self):
        from repro.parallel.halo import auto_axes
        object.__setattr__(self, "mesh", auto_axes(self.mesh))

    def _axis_size(self, name: str) -> int:
        return self.mesh.shape[name]

    def _resolve(self, dim_name: str, size: int) -> Any:
        rules = dict(PROFILES[self.profile])
        if self.state_over_data:
            rules["ssm_headdim"] = ("data",)
            rules["kv_seq"] = ("model+data", "model")
        for cand in rules.get(dim_name, ()):
            axes = tuple(cand.split("+")) if "+" in cand else (cand,)
            axes = tuple(a for a in axes if a in self.mesh.axis_names)
            if not axes:
                continue
            total = 1
            for a in axes:
                total *= self._axis_size(a)
            if size % total == 0 and size > 0:
                return axes if len(axes) > 1 else axes[0]
        return None

    def spec(self, dims: tuple[str | None, ...], shape: tuple[int, ...]) -> P:
        if len(dims) != len(shape):
            raise ValueError(f"dims {dims} vs shape {shape}")
        taken: set[str] = set()
        entries = []
        for d, s in zip(dims, shape):
            r = None if d is None else self._resolve(d, s)
            # one mesh axis may appear at most once in a spec
            flat = (r,) if isinstance(r, str) else (r or ())
            if r is not None and any(a in taken for a in flat):
                r = None
            if r is not None:
                taken.update(flat)
            entries.append(r)
        return P(*entries)

    def sharding(self, dims: tuple[str | None, ...], shape: tuple[int, ...]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(dims, shape))

    def opt_spec(self, dims: tuple[str | None, ...], shape: tuple[int, ...]) -> P:
        """ZeRO-1 spec for optimizer state / master params: the normal spec,
        plus the largest still-unsharded dim additionally sharded over the
        data axes.  Grads reduce-scatter into it; updated params all-gather
        out — SPMD emits both from the sharding mismatch alone."""
        base = self.spec(dims, shape)
        taken = set()
        for e in base:
            if e is None:
                continue
            taken.update(e if isinstance(e, tuple) else (e,))
        axes = tuple(a for a in ("pod", "data") if a in self.mesh.axis_names
                     and a not in taken)
        if not axes:
            return base
        ways = 1
        for a in axes:
            ways *= self._axis_size(a)
        # largest unsharded dim divisible by the data ways
        cands = [(s, i) for i, s in enumerate(shape)
                 if base[i] is None and s % ways == 0 and s >= ways]
        if not cands:
            return base
        _, idx = max(cands)
        entries = list(base) + [None] * (len(shape) - len(base))
        entries[idx] = axes if len(axes) > 1 else axes[0]
        return P(*entries)

    def opt_sharding(self, dims, shape) -> NamedSharding:
        return NamedSharding(self.mesh, self.opt_spec(dims, shape))

    def constrain(self, x: jax.Array, dims: tuple[str | None, ...]) -> jax.Array:
        """with_sharding_constraint by logical dims (inside jit)."""
        return jax.lax.with_sharding_constraint(x, self.sharding(dims, x.shape))


def tree_shardings(sharder: Sharder, tree_dims, tree_shapes):
    """Map a pytree of logical-dims tuples + shapes to NamedShardings."""
    return jax.tree.map(
        lambda dims, shp: sharder.sharding(tuple(dims), tuple(shp)),
        tree_dims,
        tree_shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )
