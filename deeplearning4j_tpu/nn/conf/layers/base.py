"""Layer base classes: declarative config + pure-functional compute in one class.

Design note (TPU-first): the reference splits each layer into a config class
(nn/conf/layers/*) and an imperative implementation with hand-written backprop
(nn/layers/*, ref nn/api/Layer.java:38 activate/backpropGradient). Here a layer is a
single declarative object whose `forward` is a *pure function* — the network traces all
layers into one XLA computation and `jax.grad` replaces `backpropGradient` entirely.
There is no per-layer op dispatch at runtime.

Serde parity: like the reference's Jackson JSON round-trip
(nn/conf/NeuralNetConfiguration.java:328-349), every layer serializes to a dict with an
"@class" discriminator via LAYER_REGISTRY.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common.enums import Activation, GradientNormalization, WeightInit
from deeplearning4j_tpu.nn.activations import apply_activation
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.weights import init_weights

LAYER_REGISTRY: dict[str, type] = {}

# Param keys regularized by l1/l2 (weights only, not biases — matching reference
# LayerValidation/BaseLayer l1/l2 semantics).
WEIGHT_KEY_PREFIXES = ("W", "RW", "gamma_w", "w_")


def register_layer(cls):
    """Register for serde AND wrap __init__ to record explicitly-passed kwargs.

    Explicit-set tracking is what lets the builder's global defaults apply only to
    fields the user did not set (ref NeuralNetConfiguration.Builder semantics, where
    unset layer fields are null until the global conf fills them). Without it, an
    explicit value equal to the class default would be silently overridden."""
    orig_init = cls.__init__
    field_names = [f.name for f in dataclasses.fields(cls)]

    def __init__(self, *args, **kwargs):
        orig_init(self, *args, **kwargs)
        explicit = set(kwargs.keys()) | set(field_names[:len(args)])
        object.__setattr__(self, "_explicit", explicit)

    cls.__init__ = __init__
    LAYER_REGISTRY[cls.__name__] = cls
    return cls


def _serde_value(v):
    import enum
    if isinstance(v, enum.Enum):
        return v.value
    if isinstance(v, InputType):
        return {"@input_type": v.to_dict()}
    if isinstance(v, BaseLayerConf):
        return v.to_dict()
    if isinstance(v, (list, tuple)):
        return [_serde_value(x) for x in v]
    if isinstance(v, dict):
        return {k: _serde_value(x) for k, x in v.items()}
    if hasattr(v, "to_dict"):
        return v.to_dict()
    return v


@dataclass
class BaseLayerConf:
    """Common fields mirroring ref nn/conf/layers/Layer + BaseLayer builders."""
    name: Optional[str] = None
    activation: Activation = Activation.IDENTITY
    weight_init: WeightInit = WeightInit.XAVIER
    dist: Optional[dict] = None
    bias_init: float = 0.0
    l1: float = 0.0
    l2: float = 0.0
    l1_bias: float = 0.0
    l2_bias: float = 0.0
    dropout: float = 0.0  # retain probability; 0 disables (ref util/Dropout.java semantics)
    updater: Optional[dict] = None  # per-layer updater override (serialized BaseUpdater)
    frozen: bool = False  # FrozenLayer semantics (ref nn/layers/FrozenLayer.java)
    gradient_normalization: GradientNormalization = GradientNormalization.NoNormalization
    gradient_normalization_threshold: float = 1.0
    # Per-param partition specs for model-parallel training: param name -> a
    # per-dimension list of mesh-axis names (or None), e.g. {"W": [None, "model"]}
    # for a Megatron column-parallel Dense kernel. None = use the trainer's auto
    # policy (parallel/sharded.py). JSON round-trips as plain dict-of-lists, so
    # sharded configs ship across processes like every other conf field.
    weight_sharding: Optional[Dict[str, Any]] = None

    # ---------------- shape / params ----------------
    def get_output_type(self, input_type: InputType) -> InputType:
        raise NotImplementedError

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        """Infer nIn from the previous layer's output type (ListBuilder pass)."""
        return None

    def init_params(self, key: jax.Array, input_type: InputType, dtype=jnp.float32
                    ) -> Dict[str, jnp.ndarray]:
        return {}

    def init_state(self, input_type: InputType, dtype=jnp.float32) -> Dict[str, Any]:
        return {}

    # ---------------- compute ----------------
    def forward(self, params: Dict[str, jnp.ndarray], state: Dict[str, Any],
                x: jnp.ndarray, *, train: bool, rng: Optional[jax.Array] = None,
                mask: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Dict[str, Any], Optional[jnp.ndarray]]:
        """Returns (output, new_state, output_mask)."""
        raise NotImplementedError

    # Loss layers override these.
    def is_output_layer(self) -> bool:
        return False

    def has_params(self) -> bool:
        return True

    # ---------------- regularization ----------------
    def regularization_score(self, params: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        s = jnp.asarray(0.0, jnp.float32)
        if self.frozen:
            return s  # frozen layers contribute no regularization (FrozenLayer)
        for k, p in params.items():
            is_weight = any(k.startswith(pref) for pref in WEIGHT_KEY_PREFIXES)
            l1 = self.l1 if is_weight else self.l1_bias
            l2 = self.l2 if is_weight else self.l2_bias
            if l1:
                s = s + l1 * jnp.sum(jnp.abs(p))
            if l2:
                s = s + 0.5 * l2 * jnp.sum(jnp.square(p))
        return s

    # ---------------- helpers ----------------
    def _act(self, z):
        return apply_activation(self.activation, z)

    def _winit(self, key, shape, fan_in, fan_out, dtype):
        return init_weights(key, shape, fan_in, fan_out, self.weight_init,
                            distribution=self.dist, dtype=dtype)

    # ---------------- serde ----------------
    def to_dict(self) -> dict:
        d = {}
        for f in dataclasses.fields(self):
            d[f.name] = _serde_value(getattr(self, f.name))
        d["@class"] = type(self).__name__
        return d

    @staticmethod
    def from_dict(d: dict) -> "BaseLayerConf":
        d = dict(d)
        cls = LAYER_REGISTRY[d.pop("@class")]
        kwargs = {}
        fields = {f.name: f for f in dataclasses.fields(cls)}
        hints = _resolved_hints(cls)
        for k, v in d.items():
            if k not in fields:
                continue
            kwargs[k] = _deserde_value(hints.get(k), v)
        return cls(**kwargs)


def _resolved_hints(cls):
    import typing
    try:
        return typing.get_type_hints(cls)
    except Exception:
        return {}


def _deserde_value(hint, v):
    import enum as _enum
    import typing
    if v is None:
        return None
    if isinstance(v, dict) and "@input_type" in v:
        return InputType.from_dict(v["@input_type"])
    if isinstance(v, dict) and "@class" in v:
        name = v["@class"]
        if name in LAYER_REGISTRY:
            return BaseLayerConf.from_dict(v)
        from deeplearning4j_tpu.nn.updater.updaters import UPDATER_REGISTRY, BaseUpdater
        if name in UPDATER_REGISTRY:
            return BaseUpdater.from_dict(v)
    origin = typing.get_origin(hint)
    if origin is typing.Union:
        args = [a for a in typing.get_args(hint) if a is not type(None)]
        hint = args[0] if len(args) == 1 else None
        return _deserde_value(hint, v)
    if isinstance(hint, type) and issubclass(hint, _enum.Enum):
        return hint(v)
    if isinstance(v, list):
        return tuple(v) if origin is tuple else [
            _deserde_value(None, x) for x in v]
    return v


def layer_scope(layer, name) -> "jax.named_scope":
    """The name a layer's or vertex's operations carry in the compiled
    program: `dl4j.<its class>/<its name>` (telemetry.profiler.op_scopes)."""
    return jax.named_scope(f"dl4j.{type(layer).__name__}/{name}")


def apply_dropout(x: jnp.ndarray, retain_prob: float, rng: jax.Array) -> jnp.ndarray:
    """Inverted dropout on layer *input* (ref util/Dropout.java applied in
    applyDropOutIfNecessary before the layer op)."""
    keep = jax.random.bernoulli(rng, retain_prob, x.shape)
    return jnp.where(keep, x / retain_prob, 0.0).astype(x.dtype)


@dataclass
class FeedForwardLayerConf(BaseLayerConf):
    """Base for layers with explicit n_in/n_out (ref nn/conf/layers/FeedForwardLayer)."""
    n_in: int = 0
    n_out: int = 0

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_in == 0 or override:
            self.n_in = input_type.flat_size() if input_type.kind in ("cnn", "cnn_flat") \
                else input_type.size

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)
