"""Variational autoencoder, RBM, and center-loss output layers — the pretraining family.

Parity targets:
- ref nn/conf/layers/variational/VariationalAutoencoder.java:47 (config surface:
  encoderLayerSizes/decoderLayerSizes/pzxActivationFn/numSamples/reconstructionDistribution)
  and nn/layers/variational/VariationalAutoencoder.java (1,151 LoC of hand-written
  forward/backprop) — here the ELBO is a pure function and `jax.grad` replaces the whole
  backprop half.
- ref nn/conf/layers/variational/{Gaussian,Bernoulli,Exponential,Composite,
  LossFunctionWrapper}ReconstructionDistribution.java + ReconstructionDistribution.java.
- ref nn/conf/layers/RBM.java:65 + nn/layers/feedforward/rbm/RBM.java (CD-k gibbs chain,
  contrastiveDivergence at :102). CD is not the gradient of a tractable scalar, so RBM
  exposes `pretrain_grads` (direct positive-phase − negative-phase statistics) instead of
  `pretrain_score`; the gibbs chain is a fixed-k unrolled jittable loop.
- ref nn/conf/layers/CenterLossOutputLayer.java:63 (alpha/lambda/gradientCheck) +
  nn/layers/training/CenterLossOutputLayer.java + params/CenterLossParamInitializer.java:52
  (CENTER_KEY "cL", centers shape [numClasses, nIn]).

TPU notes: every distribution's log-prob is elementwise math over the decoder's fused
matmul output; num_samples Monte-Carlo samples are batched via a leading sample axis so
the decoder matmuls stay large on the MXU instead of looping.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common.enums import Activation, LossFunction
from deeplearning4j_tpu.nn.activations import apply_activation
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (
    FeedForwardLayerConf, register_layer)
from deeplearning4j_tpu.nn.conf.layers.feedforward import OutputLayer
from deeplearning4j_tpu.nn.losses import compute_loss

DIST_REGISTRY: dict[str, type] = {}

_HALF_LOG_2PI = 0.5 * math.log(2 * math.pi)


def register_dist(cls):
    DIST_REGISTRY[cls.__name__] = cls
    return cls


class ReconstructionDistribution:
    """p(x|z) head for the VAE decoder (ref ReconstructionDistribution.java).

    `param_size(data_size)` gives the decoder output width; `neg_log_prob` consumes the
    decoder pre-activations and returns a per-example negative log-likelihood."""

    def param_size(self, data_size: int) -> int:
        raise NotImplementedError

    def neg_log_prob(self, x: jnp.ndarray, preout: jnp.ndarray) -> jnp.ndarray:
        """Per-example -log p(x|z); x (batch, d), preout (batch, param_size(d))."""
        raise NotImplementedError

    def generate_at_mean(self, preout: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def generate_random(self, rng: jax.Array, preout: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def has_log_prob(self) -> bool:
        """False for LossFunctionWrapper (ref hasLossFunction semantics)."""
        return True

    # ------------- serde -------------
    def to_dict(self) -> dict:
        d = dict(self.__dict__)
        d["@dist"] = type(self).__name__
        return d

    @staticmethod
    def from_dict(d: dict) -> "ReconstructionDistribution":
        d = dict(d)
        cls = DIST_REGISTRY[d.pop("@dist")]
        return cls._from_fields(d)

    @classmethod
    def _from_fields(cls, d: dict):
        import enum as _enum
        import typing
        kwargs = {}
        hints = typing.get_type_hints(cls.__init__)
        import inspect
        sig = inspect.signature(cls.__init__)
        for k, v in d.items():
            if k not in sig.parameters:
                continue
            hint = hints.get(k)
            if isinstance(hint, type) and issubclass(hint, _enum.Enum) and v is not None:
                v = hint(v)
            kwargs[k] = v
        return cls(**kwargs)


@register_dist
class GaussianReconstructionDistribution(ReconstructionDistribution):
    """N(mu, sigma^2) per output; decoder emits [mu_preact | log(sigma^2)]
    (ref GaussianReconstructionDistribution.java — activation applies to the mean half
    only, log-variance half stays identity)."""

    def __init__(self, activation: Activation = Activation.IDENTITY):
        self.activation = Activation(activation)

    def param_size(self, data_size):
        return 2 * data_size

    def _split(self, preout):
        d = preout.shape[-1] // 2
        mu = apply_activation(self.activation, preout[..., :d])
        log_var = preout[..., d:]
        return mu, log_var

    def neg_log_prob(self, x, preout):
        mu, log_var = self._split(preout)
        nll = _HALF_LOG_2PI + 0.5 * log_var + 0.5 * jnp.square(x - mu) / jnp.exp(log_var)
        return jnp.sum(nll, axis=-1)

    def generate_at_mean(self, preout):
        return self._split(preout)[0]

    def generate_random(self, rng, preout):
        mu, log_var = self._split(preout)
        return mu + jnp.exp(0.5 * log_var) * jax.random.normal(rng, mu.shape, mu.dtype)


@register_dist
class BernoulliReconstructionDistribution(ReconstructionDistribution):
    """Bernoulli(p) per output with p = act(preout); default sigmoid
    (ref BernoulliReconstructionDistribution.java)."""

    def __init__(self, activation: Activation = Activation.SIGMOID):
        self.activation = Activation(activation)

    def param_size(self, data_size):
        return data_size

    def neg_log_prob(self, x, preout):
        if self.activation == Activation.SIGMOID:
            # fused stable form: softplus(z) - x*z
            nll = jax.nn.softplus(preout) - x * preout
        else:
            p = jnp.clip(apply_activation(self.activation, preout), 1e-7, 1 - 1e-7)
            nll = -(x * jnp.log(p) + (1 - x) * jnp.log1p(-p))
        return jnp.sum(nll, axis=-1)

    def generate_at_mean(self, preout):
        return apply_activation(self.activation, preout)

    def generate_random(self, rng, preout):
        p = apply_activation(self.activation, preout)
        return jax.random.bernoulli(rng, p).astype(p.dtype)


@register_dist
class ExponentialReconstructionDistribution(ReconstructionDistribution):
    """Exp(lambda) with lambda = exp(act(preout)) so the rate stays positive
    (ref ExponentialReconstructionDistribution.java: gamma = activation output,
    log p(x) = gamma - x*exp(gamma))."""

    def __init__(self, activation: Activation = Activation.IDENTITY):
        self.activation = Activation(activation)

    def param_size(self, data_size):
        return data_size

    def neg_log_prob(self, x, preout):
        gamma = apply_activation(self.activation, preout)
        return jnp.sum(x * jnp.exp(gamma) - gamma, axis=-1)

    def generate_at_mean(self, preout):
        gamma = apply_activation(self.activation, preout)
        return jnp.exp(-gamma)  # mean = 1/lambda

    def generate_random(self, rng, preout):
        gamma = apply_activation(self.activation, preout)
        u = jax.random.uniform(rng, gamma.shape, gamma.dtype, 1e-7, 1.0)
        return -jnp.log(u) * jnp.exp(-gamma)  # inverse-CDF


@register_dist
class CompositeReconstructionDistribution(ReconstructionDistribution):
    """Different distributions over slices of the data vector
    (ref CompositeReconstructionDistribution.java). `components` is a list of
    (data_size, distribution) pairs in data order."""

    def __init__(self, components: Sequence[Tuple[int, Any]] = ()):
        comps = []
        for size, dist in components:
            if isinstance(dist, dict):
                dist = ReconstructionDistribution.from_dict(dist)
            comps.append((int(size), dist))
        self.components = comps

    def param_size(self, data_size):
        assert data_size == sum(s for s, _ in self.components), \
            f"composite sizes {self.components} != data size {data_size}"
        return sum(d.param_size(s) for s, d in self.components)

    def _slices(self):
        xo = po = 0
        for size, dist in self.components:
            ps = dist.param_size(size)
            yield (xo, size, po, ps, dist)
            xo += size
            po += ps

    def neg_log_prob(self, x, preout):
        total = 0.0
        for xo, xs, po, ps, dist in self._slices():
            total = total + dist.neg_log_prob(x[..., xo:xo + xs], preout[..., po:po + ps])
        return total

    def generate_at_mean(self, preout):
        return jnp.concatenate([d.generate_at_mean(preout[..., po:po + ps])
                                for _, _, po, ps, d in self._slices()], axis=-1)

    def generate_random(self, rng, preout):
        outs = []
        for _, _, po, ps, d in self._slices():
            rng, sub = jax.random.split(rng)
            outs.append(d.generate_random(sub, preout[..., po:po + ps]))
        return jnp.concatenate(outs, axis=-1)

    def has_log_prob(self):
        return all(d.has_log_prob() for _, d in self.components)

    def to_dict(self):
        return {"@dist": "CompositeReconstructionDistribution",
                "components": [[s, d.to_dict()] for s, d in self.components]}


@register_dist
class LossFunctionWrapper(ReconstructionDistribution):
    """Arbitrary loss function as a pseudo reconstruction 'distribution'
    (ref LossFunctionWrapper.java — hasLossFunction()=true; reconstruction
    *probability* is unavailable, only the loss)."""

    def __init__(self, activation: Activation = Activation.IDENTITY,
                 loss_fn: LossFunction = LossFunction.MSE):
        self.activation = Activation(activation)
        self.loss_fn = LossFunction(loss_fn)

    def param_size(self, data_size):
        return data_size

    def has_log_prob(self):
        return False

    def neg_log_prob(self, x, preout):
        # per-example loss; compute_loss is mean-over-examples so scale back up per row
        # by computing it row-wise via vmap-free elementwise math: reuse compute_loss on
        # each example is wasteful — instead compute on full batch with examples kept.
        act = apply_activation(self.activation, preout)
        if self.loss_fn == LossFunction.MSE:
            per = jnp.sum(jnp.square(x - act), axis=-1)
        elif self.loss_fn == LossFunction.L1:
            per = jnp.sum(jnp.abs(x - act), axis=-1)
        elif self.loss_fn == LossFunction.XENT:
            p = jnp.clip(act, 1e-7, 1 - 1e-7)
            per = -jnp.sum(x * jnp.log(p) + (1 - x) * jnp.log1p(-p), axis=-1)
        else:
            raise ValueError(f"LossFunctionWrapper: unsupported {self.loss_fn}")
        return per

    def generate_at_mean(self, preout):
        return apply_activation(self.activation, preout)

    def generate_random(self, rng, preout):
        return self.generate_at_mean(preout)


# ======================================================================= VAE


@register_layer
@dataclass
class VariationalAutoencoder(FeedForwardLayerConf):
    """VAE as a single layer (ref conf/layers/variational/VariationalAutoencoder.java:47).

    Supervised forward = encoder -> mean of q(z|x) (ref impl activate()); pretraining
    maximizes the ELBO: E_q[log p(x|z)] - KL(q(z|x) || N(0,I)), with `num_samples`
    Monte-Carlo samples batched on a leading axis. n_out is the latent size.

    Param keys use the W_*/b_* convention so WEIGHT_KEY_PREFIXES-based l1/l2 applies to
    weights only, mirroring ref VariationalAutoencoderParamInitializer."""
    encoder_layer_sizes: Tuple[int, ...] = (100,)
    decoder_layer_sizes: Tuple[int, ...] = (100,)
    pzx_activation: Activation = Activation.IDENTITY
    num_samples: int = 1
    reconstruction_distribution: Optional[Any] = None  # ReconstructionDistribution

    def __post_init__(self):
        self.encoder_layer_sizes = tuple(self.encoder_layer_sizes)
        self.decoder_layer_sizes = tuple(self.decoder_layer_sizes)
        if self.reconstruction_distribution is None:
            # ref Builder default: Gaussian with TANH
            self.reconstruction_distribution = GaussianReconstructionDistribution(
                Activation.TANH)
        elif isinstance(self.reconstruction_distribution, dict):
            self.reconstruction_distribution = ReconstructionDistribution.from_dict(
                self.reconstruction_distribution)

    @property
    def dist_head(self) -> ReconstructionDistribution:
        return self.reconstruction_distribution

    def is_pretrain_layer(self):
        return True

    # ---------------- params ----------------
    def init_params(self, key, input_type, dtype=jnp.float32):
        p = {}
        sizes = [self.n_in] + list(self.encoder_layer_sizes)
        keys = jax.random.split(key, len(self.encoder_layer_sizes)
                                + len(self.decoder_layer_sizes) + 3)
        ki = 0
        for i in range(len(self.encoder_layer_sizes)):
            fi, fo = sizes[i], sizes[i + 1]
            p[f"W_e{i}"] = self._winit(keys[ki], (fi, fo), fi, fo, dtype)
            p[f"b_e{i}"] = jnp.full((fo,), self.bias_init, dtype)
            ki += 1
        enc_out = sizes[-1]
        p["W_zm"] = self._winit(keys[ki], (enc_out, self.n_out), enc_out, self.n_out,
                                dtype)
        p["b_zm"] = jnp.zeros((self.n_out,), dtype)
        ki += 1
        p["W_zv"] = self._winit(keys[ki], (enc_out, self.n_out), enc_out, self.n_out,
                                dtype)
        p["b_zv"] = jnp.zeros((self.n_out,), dtype)
        ki += 1
        dsizes = [self.n_out] + list(self.decoder_layer_sizes)
        for i in range(len(self.decoder_layer_sizes)):
            fi, fo = dsizes[i], dsizes[i + 1]
            p[f"W_d{i}"] = self._winit(keys[ki], (fi, fo), fi, fo, dtype)
            p[f"b_d{i}"] = jnp.full((fo,), self.bias_init, dtype)
            ki += 1
        px = self.dist_head.param_size(self.n_in)
        p["W_x"] = self._winit(keys[ki], (dsizes[-1], px), dsizes[-1], px, dtype)
        p["b_x"] = jnp.zeros((px,), dtype)
        return p

    # ---------------- compute ----------------
    def _encode(self, params, x):
        h = x
        for i in range(len(self.encoder_layer_sizes)):
            h = self._act(h @ params[f"W_e{i}"] + params[f"b_e{i}"])
        mu = apply_activation(self.pzx_activation, h @ params["W_zm"] + params["b_zm"])
        log_var = h @ params["W_zv"] + params["b_zv"]
        return mu, log_var

    def _decode(self, params, z):
        h = z
        for i in range(len(self.decoder_layer_sizes)):
            h = self._act(h @ params[f"W_d{i}"] + params[f"b_d{i}"])
        return h @ params["W_x"] + params["b_x"]

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        mu, _ = self._encode(params, x)
        return mu, state, mask

    def pretrain_score(self, params, x, rng):
        """-ELBO, mean over the minibatch (ref impl computeGradientAndScore for
        pretrain mode — negated since we minimize)."""
        mu, log_var = self._encode(params, x)
        kl = -0.5 * jnp.sum(1.0 + log_var - jnp.square(mu) - jnp.exp(log_var), axis=-1)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        # (num_samples, batch, latent): batched sampling keeps decoder matmuls MXU-sized
        eps = jax.random.normal(rng, (self.num_samples,) + mu.shape, mu.dtype)
        z = mu[None] + jnp.exp(0.5 * log_var)[None] * eps
        preout = self._decode(params, z)
        nll = self.dist_head.neg_log_prob(x[None], preout)  # (num_samples, batch)
        return jnp.mean(kl + jnp.mean(nll, axis=0))

    # ---------------- inference-time utilities (ref impl public API) ----------------
    def reconstruction_log_probability(self, params, x, num_samples: int = 5,
                                       rng: Optional[jax.Array] = None):
        """log (1/S sum_s p(x|z_s)), z_s ~ q(z|x) — ref reconstructionLogProbability."""
        if not self.dist_head.has_log_prob():
            raise ValueError("reconstruction distribution has no log probability "
                             "(LossFunctionWrapper); use reconstruction_error")
        if rng is None:
            rng = jax.random.PRNGKey(0)
        mu, log_var = self._encode(params, x)
        eps = jax.random.normal(rng, (num_samples,) + mu.shape, mu.dtype)
        z = mu[None] + jnp.exp(0.5 * log_var)[None] * eps
        log_p = -self.dist_head.neg_log_prob(x[None], self._decode(params, z))
        return jax.scipy.special.logsumexp(log_p, axis=0) - jnp.log(float(num_samples))

    def reconstruction_error(self, params, x):
        """Deterministic reconstruction loss at the posterior mean
        (ref reconstructionError, defined for LossFunctionWrapper)."""
        mu, _ = self._encode(params, x)
        return self.dist_head.neg_log_prob(x, self._decode(params, mu))

    def generate_at_mean_given_z(self, params, z):
        return self.dist_head.generate_at_mean(self._decode(params, z))

    def generate_random_given_z(self, params, z, rng):
        return self.dist_head.generate_random(rng, self._decode(params, z))


# ======================================================================= RBM


@register_layer
@dataclass
class RBM(FeedForwardLayerConf):
    """Restricted Boltzmann machine (ref conf/layers/RBM.java:65, impl
    nn/layers/feedforward/rbm/RBM.java). Supervised forward = propUp through the layer
    activation; pretraining = CD-k via `pretrain_grads` (gibbs chain at ref :102-151,
    unrolled for static k — each step is two fused matmuls on the MXU).

    hidden_unit/visible_unit in {binary, gaussian, rectified, softmax}
    (ref RBM.HiddenUnit/VisibleUnit enums)."""
    hidden_unit: str = "binary"
    visible_unit: str = "binary"
    k: int = 1
    sparsity: float = 0.0

    def is_pretrain_layer(self):
        return True

    def init_params(self, key, input_type, dtype=jnp.float32):
        return {
            "W": self._winit(key, (self.n_in, self.n_out), self.n_in, self.n_out, dtype),
            "b": jnp.full((self.n_out,), self.bias_init, dtype),   # hidden bias
            "vb": jnp.zeros((self.n_in,), dtype),                   # visible bias
        }

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        return self._act(x @ params["W"] + params["b"]), state, mask

    # ---------------- gibbs machinery (ref propUp/propDown at :224/:276) ------------
    def _unit_mean(self, kind, z):
        if kind == "binary":
            return jax.nn.sigmoid(z)
        if kind == "gaussian":
            return z
        if kind == "rectified":
            return jnp.maximum(z, 0.0)
        if kind == "softmax":
            return jax.nn.softmax(z, axis=-1)
        raise ValueError(f"unknown RBM unit type {kind!r}")

    def _unit_sample(self, kind, mean, z, rng):
        if kind == "binary":
            return jax.random.bernoulli(rng, mean).astype(mean.dtype)
        if kind == "gaussian":
            return mean + jax.random.normal(rng, mean.shape, mean.dtype)
        if kind == "rectified":
            # NReLU sampling (ref :241): max(0, z + N(0, sigmoid(z)))
            noise = jax.random.normal(rng, z.shape, z.dtype) * jnp.sqrt(
                jax.nn.sigmoid(z) + 1e-8)
            return jnp.maximum(z + noise, 0.0)
        if kind == "softmax":
            return jax.nn.one_hot(
                jax.random.categorical(rng, jnp.log(mean + 1e-12), axis=-1),
                mean.shape[-1], dtype=mean.dtype)
        raise ValueError(kind)

    def prop_up(self, params, v):
        z = v @ params["W"] + params["b"]
        return self._unit_mean(self.hidden_unit, z), z

    def prop_down(self, params, h):
        z = h @ params["W"].T + params["vb"]
        return self._unit_mean(self.visible_unit, z), z

    def pretrain_grads(self, params, x, rng):
        """CD-k gradient estimate: positive phase stats minus negative phase stats
        (ref contrastiveDivergence :102 / computeGradientAndScore :114). Returns
        (grads_dict, monitoring_score). Gradients point in the *descent* direction
        (they are subtracted by the updater, like autodiff grads)."""
        n = x.shape[0]
        h0_mean, h0_z = self.prop_up(params, x)
        rng, sub = jax.random.split(rng)
        h = self._unit_sample(self.hidden_unit, h0_mean, h0_z, sub)
        v_mean = x
        for _ in range(self.k):  # static k: unrolled, each iter two MXU matmuls
            v_mean, v_z = self.prop_down(params, h)
            rng, sub = jax.random.split(rng)
            v = self._unit_sample(self.visible_unit, v_mean, v_z, sub)
            hk_mean, hk_z = self.prop_up(params, v)
            rng, sub = jax.random.split(rng)
            h = self._unit_sample(self.hidden_unit, hk_mean, hk_z, sub)
        # gradient of -log p(v): -(positive - negative)
        gW = -(x.T @ h0_mean - v.T @ hk_mean) / n
        gb = -jnp.mean(h0_mean - hk_mean, axis=0)
        gvb = -jnp.mean(x - v, axis=0)
        if self.sparsity > 0:
            # sparsity penalty pushes mean hidden activation toward the target
            gb = gb + (jnp.mean(h0_mean, axis=0) - self.sparsity)
        score = jnp.mean(jnp.sum(jnp.square(x - v_mean), axis=-1))
        return {"W": gW, "b": gb, "vb": gvb}, score


# ======================================================== CenterLossOutputLayer


@register_layer
@dataclass
class CenterLossOutputLayer(OutputLayer):
    """Output layer with an auxiliary center loss (ref conf/layers/
    CenterLossOutputLayer.java:63, impl nn/layers/training/CenterLossOutputLayer.java).

    Centers `cL` have shape (n_out classes, n_in features)
    (ref CenterLossParamInitializer.java:52,80). Total score = base loss +
    lambda/2 * mean_i ||f_i - c_{y_i}||^2.

    gradient_check=True (default): centers are ordinary params of the combined scalar —
    exactly finite-difference checkable. gradient_check=False mirrors the reference's
    deployed behavior where centers move by an alpha-scaled EMA toward class feature
    means, decoupled from lambda (stop-gradient split form)."""
    alpha: float = 0.05
    lambda_: float = 2e-4
    gradient_check: bool = True

    def init_params(self, key, input_type, dtype=jnp.float32):
        p = super().init_params(key, input_type, dtype)
        p["cL"] = jnp.zeros((self.n_out, self.n_in), dtype)
        return p

    def regularization_score(self, params):
        # centers are never regularized (ref getL1ByParam/getL2ByParam return 0 for cL)
        return super().regularization_score({k: v for k, v in params.items()
                                             if k != "cL"})

    def compute_score(self, params, x, labels, mask=None):
        base = compute_loss(self.loss_fn, labels, self.preout(params, x),
                            self.activation, mask)
        centers = params["cL"]
        idx = jnp.argmax(labels, axis=-1)
        c = centers[idx]  # (batch, n_in) gather
        n = x.shape[0]

        def _row_term(a, b):
            per_row = jnp.sum(jnp.square(a - b), axis=-1)
            if mask is not None:
                # same masked-loss policy as compute_loss: zero masked rows, divide
                # by minibatch size — padding rows must not drag their class center
                m = jnp.reshape(mask, (n, -1))[:, 0].astype(per_row.dtype)
                per_row = per_row * m
            return jnp.sum(per_row) / n

        if self.gradient_check:
            center_term = 0.5 * self.lambda_ * _row_term(x, c)
        else:
            # split form: features feel lambda, centers feel alpha (ref backprop :63
            # applies alpha directly to the center delta, any updater on top)
            feat = 0.5 * self.lambda_ * _row_term(x, jax.lax.stop_gradient(c))
            cent = 0.5 * self.alpha * _row_term(jax.lax.stop_gradient(x), c)
            center_term = feat + cent
        return base + center_term
