"""Architecture registry: name -> (module, example_input) factories.

Plays the role of the reference's per-framework predictor dispatch
(reference pkg/apis/serving/v1beta1/predictor.go:33-59 picks a server image
by framework name): here the "framework" is an architecture string in the
model's config, and the factory yields a Flax module the JaxEngine can
compile.  Registration is open — user models plug in with
`register_model("myarch", factory)` exactly like custom predictors do in the
reference (predictor_custom.go).
"""

from typing import Any, Callable, Dict, NamedTuple, Tuple


class ModelSpec(NamedTuple):
    module: Any                # flax.linen.Module
    example: Any               # single-instance example input (batch dim 1)


_REGISTRY: Dict[str, Callable[..., Tuple[Any, Any]]] = {}

# Built-ins resolve lazily (module_path, builder_name) so importing the
# registry — e.g. control-plane code listing architectures — doesn't pay
# jax/flax initialization.  The model modules import on first create_model.
_LAZY_BUILTINS: Dict[str, Tuple[str, str]] = {
    "resnet50": ("kfserving_tpu.models.resnet", "create_resnet50"),
    "bert": ("kfserving_tpu.models.bert", "_create_bert_base"),
    "bert_tiny": ("kfserving_tpu.models.bert", "_create_bert_tiny"),
    "vit_b16": ("kfserving_tpu.models.vit", "_create_vit_b16"),
    "vit_tiny": ("kfserving_tpu.models.vit", "_create_vit_tiny"),
    "mlp": ("kfserving_tpu.models.mlp", "create_mlp"),
    "decoder": ("kfserving_tpu.models.decoder", "_create_decoder_small"),
    "decoder_tiny": ("kfserving_tpu.models.decoder",
                     "_create_decoder_tiny"),
    "olmoe": ("kfserving_tpu.models.olmoe", "_create_olmoe"),
    "olmoe_tiny": ("kfserving_tpu.models.olmoe", "_create_olmoe_tiny"),
    "nemotron_h": ("kfserving_tpu.models.nemotron_h", "_create_nemotron_h"),
    "nemotron_h_tiny": ("kfserving_tpu.models.nemotron_h",
                        "_create_nemotron_h_tiny"),
    "mellum": ("kfserving_tpu.models.mellum", "_create_mellum"),
    "mellum_tiny": ("kfserving_tpu.models.mellum", "_create_mellum_tiny"),
    "falcon_h1": ("kfserving_tpu.models.falcon_h1", "_create_falcon_h1"),
    "falcon_h1_tiny": ("kfserving_tpu.models.falcon_h1",
                       "_create_falcon_h1_tiny"),
    "deepseek_v3": ("kfserving_tpu.models.deepseek_v3",
                    "_create_deepseek_v3"),
    "deepseek_v3_tiny": ("kfserving_tpu.models.deepseek_v3",
                         "_create_deepseek_v3_tiny"),
}


def register_model(name: str, factory: Callable[..., Tuple[Any, Any]]):
    _REGISTRY[name] = factory


def list_models():
    return sorted(set(_REGISTRY) | set(_LAZY_BUILTINS))


def _resolve(name: str) -> Callable[..., Tuple[Any, Any]]:
    if name in _REGISTRY:
        return _REGISTRY[name]
    if name in _LAZY_BUILTINS:
        import importlib

        module_path, attr = _LAZY_BUILTINS[name]
        factory = getattr(importlib.import_module(module_path), attr)
        _REGISTRY[name] = factory
        return factory
    raise KeyError(
        f"unknown architecture {name!r}; known: {list_models()}")


def create_model(name: str, **kwargs) -> ModelSpec:
    module, example = _resolve(name)(**kwargs)
    return ModelSpec(module, example)


def init_params(spec: ModelSpec, seed: int = 0):
    """Initialize variables for a ModelSpec (random weights — serving tests
    and benchmarks measure compute, not accuracy).

    The init runs under jit: eager flax init dispatches one device op
    per parameter.  Jitted, it is one compiled program (persistent-
    cache-hot on respawn) and one execution."""
    import jax

    rng = jax.random.PRNGKey(seed)
    example = spec.example
    if isinstance(example, dict):
        init = jax.jit(lambda r: spec.module.init(r, **example))
    else:
        init = jax.jit(lambda r: spec.module.init(r, example))
    return init(rng)


def apply_fn_for(spec: ModelSpec) -> Callable:
    """A (variables, batch) -> output function in the JaxEngine calling
    convention (engine/jax_engine.py:34-44): dict batches are splatted as
    kwargs, array batches positionally.

    Dispatch is on the *runtime* batch type, not the example's: a
    dict-example model (e.g. BERT with optional attention_mask) must
    still accept a bare array when a V1 request carries only the primary
    input — the array binds to the module's first positional arg.  The
    isinstance check is static under jit tracing (it runs once per
    compiled signature)."""
    module = spec.module

    def apply(variables, batch):
        if isinstance(batch, dict):
            return module.apply(variables, **batch)
        return module.apply(variables, batch)
    return apply


