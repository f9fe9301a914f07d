"""Parameter and activation sharding rules.

Megatron-style tensor parallelism for the transformer zoo, expressed as
regex → PartitionSpec rules over flattened Flax param paths:

- q/k/v projections shard the *heads* (output) dimension on ``tp``: each
  device computes its own heads, no communication.
- attention output and MLP down projections shard the *input* dimension on
  ``tp``: XLA inserts the single per-layer psum over ICI.
- routed experts ([experts, in, out]) shard the expert *width* the same
  way; no expert kernel is replicated.
- embeddings/layernorms/heads replicate (serving batch sizes keep them
  cheap; vocab-sharded embeddings only pay off at training scale).

`shard_params` applies the first matching rule per leaf and `device_put`s
with a NamedSharding, so the engine's jitted apply becomes an SPMD program
with XLA-chosen collectives — the TPU-native replacement for the NCCL/MPI
backends the reference never had (SURVEY.md §5.8).
"""

import re
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def transformer_rules() -> Sequence[Tuple[str, P]]:
    """Rules matched against '/'-joined param paths, first match wins.
    Covers models/bert.py and models/vit.py module names."""
    return (
        # Attention projections: DenseGeneral kernels [hidden, heads, dim]
        (r".*(query|key|value)/kernel$", P(None, "tp", None)),
        (r".*(query|key|value)/bias$", P("tp", None)),
        # Attention out-proj: [heads, dim, hidden] — contract dims sharded
        (r".*attention.*/out/kernel$|.*/out/kernel$", P("tp", None, None)),
        # MLP up: [hidden, intermediate]
        (r".*(intermediate|mlp_in)/kernel$", P(None, "tp")),
        (r".*(intermediate|mlp_in)/bias$", P("tp")),
        # MLP down: [intermediate, hidden]
        (r".*(output|mlp_out)/kernel$", P("tp", None)),
        # Routed experts (models/olmoe.py), stacked [experts, in, out]:
        # the expert width splits as the MLP's, every device keeps all
        # experts, and the down projection's psum is the layer's one
        # collective.  The router stays whole.
        (r".*experts/(gate|up)$", P(None, None, "tp")),
        (r".*experts/down$", P(None, "tp", None)),
        # Everything else (embeddings, norms, heads, convs): replicated
        (r".*", P()),
    )


def _leaf_spec(path: str, shape: Tuple[int, ...],
               rules: Sequence[Tuple[str, P]],
               mesh: Optional[Mesh] = None) -> P:
    for pattern, spec in rules:
        if re.match(pattern, path):
            # Guard: a spec longer than the leaf's rank means the rule was
            # written for a different layer shape — replicate instead of
            # failing placement.
            if len(spec) > len(shape):
                return P()
            if mesh is not None:
                # Drop mesh axes that don't divide the dimension (e.g. 4
                # heads over tp=3): replicate that dim instead of failing.
                cleaned = []
                for dim, axis in zip(shape, spec):
                    size = mesh.shape.get(axis, 1) if axis else 1
                    cleaned.append(axis if dim % size == 0 else None)
                return P(*cleaned)
            return spec
    return P()


def _flatten_with_paths(tree: Any):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = []
    for keypath, leaf in flat:
        parts = []
        for k in keypath:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        paths.append(("/".join(parts), leaf))
    return paths, treedef


def param_specs(params: Any,
                rules: Optional[Sequence[Tuple[str, P]]] = None,
                mesh: Optional[Mesh] = None) -> Any:
    """PartitionSpec pytree matching `params` (for pjit in_shardings).
    With `mesh`, specs are validated against leaf shapes (non-dividing axes
    replicate)."""
    rules = rules if rules is not None else transformer_rules()
    flat, treedef = _flatten_with_paths(params)
    specs = [_leaf_spec(path, getattr(leaf, "shape", ()), rules, mesh)
             for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def shard_params(params: Any, mesh: Mesh,
                 rules: Optional[Sequence[Tuple[str, P]]] = None) -> Any:
    """Place a param pytree onto the mesh per the rules."""
    specs = param_specs(params, rules, mesh=mesh)
    return jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        params, specs)


def replicate_params(params: Any, mesh: Mesh) -> Any:
    """Fully replicate (dp-only serving; ResNet/MLP zoo)."""
    sharding = NamedSharding(mesh, P())
    return jax.tree.map(lambda leaf: jax.device_put(leaf, sharding), params)


def batch_sharding(mesh: Mesh, batch_axis: str = "dp") -> NamedSharding:
    """Input batches split along dp; all other dims replicated."""
    return NamedSharding(mesh, P(batch_axis))


def shard_batch(batch: Any, mesh: Mesh,
                batch_axis: str = "dp") -> Any:
    sharding = batch_sharding(mesh, batch_axis)
    return jax.tree.map(lambda leaf: jax.device_put(leaf, sharding), batch)


def describe(params: Any,
             rules: Optional[Sequence[Tuple[str, P]]] = None
             ) -> Dict[str, str]:
    """path -> spec string, for debugging/ops visibility."""
    rules = rules if rules is not None else transformer_rules()
    flat, _ = _flatten_with_paths(params)
    return {path: str(_leaf_spec(path, getattr(leaf, "shape", ()), rules))
            for path, leaf in flat}
