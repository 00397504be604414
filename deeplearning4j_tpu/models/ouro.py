"""Ouro-2.6B: a looped decoder, built from the keys of the published
`config.json` (https://huggingface.co/ByteDance/Ouro-2.6B, `model_type` ouro;
the LoopLM report, arXiv:2510.25741).

48 decoder layers whose whole stack runs `total_ut_steps` (4) times on every
token with the same weights. A layer has sandwich norms, an RMS norm (gain
from 1) on each sublayer's input and another on its output: h = x +
norm'(attn(norm(x))); y = h + norm'(MLP(norm(h))). The attention is causal
softmax attention on `num_attention_heads` heads of `head_dim` (all of them
k/v heads), rotary (the half-split pairing) over all of each head with
`rope_theta`, no q/k norm, no gate and no bias; the MLP a dense SwiGLU of
`intermediate_size`. After each pass come the final norm, whose output is
the next pass's input, and the early-exit gate: every pass is scored by one
untied head, and the loss is the exit distribution's expected cross-entropy
less `entropy_weight` times its entropy (`LoopExitHead`).
`early_exit_threshold` is an inference setting: in training every pass runs.

A `ComputationGraph` with one input, the ids (batch, time), and one output
scored against the next ids:

    net.fit_on_device(ids[:, :-1], ids[:, 1:], steps=n)

`share` says how many of the layers are held here (`{"layers": 8}`: one
stage of a pipeline of six, which runs its layers at every pass); the table,
the final norm, the gate and the head are whole. The graph is ids ->
`embed` -> `loop` (a `LoopedStack` of the held layers' blocks, `l<i>_attn`
and `l<i>_mlp`) -> `exit_head`.
"""
from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.common.enums import WeightInit
from deeplearning4j_tpu.models.zoo_model import ZooModel
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.decoder import (
    GatedAttention, GatedMLP, LoopedStack, LoopExitHead, PreNormResidual,
    TokenEmbedding)
from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.updater.updaters import Adam

# the published config.json, without the keys that say nothing of the shape
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152,
}


class Ouro(ZooModel):
    def __init__(self, config: Optional[dict] = None, seed: int = 123,
                 sequence_length: int = 4096, share: Optional[dict] = None,
                 updater=None, dtype: str = "float32",
                 compute_dtype: Optional[str] = "bfloat16", remat: bool = True,
                 init_std: float = 0.02, entropy_weight: float = 0.05):
        """`config`: the keys of a `config.json` of this `model_type`
        (default: the published one); `share["layers"]` of its
        `num_hidden_layers` are held (all by default)."""
        self.config = dict(PUBLISHED if config is None else config)
        super().__init__(self.config["vocab_size"], seed)
        c = self.config
        if c.get("attention_bias") or c.get("tie_word_embeddings") \
                or c["hidden_act"] != "silu" or c.get("rope_scaling") \
                or c.get("use_sliding_window") or int(c["total_ut_steps"]) < 1 \
                or any(t != "full_attention" for t in c["layer_types"]) \
                or len(c["layer_types"]) != c["num_hidden_layers"]:
            raise ValueError(
                "built: no bias, an untied head, silu, rotary unscaled, full "
                "attention in every layer, a layer type a layer, one pass or more")
        self.sequence_length = int(sequence_length)
        self.layers = int((share or {}).get("layers", c["num_hidden_layers"]))
        self.updater = updater or Adam(learning_rate=3e-4, beta1=0.9, beta2=0.95,
                                       epsilon=1e-8)
        self.dtype, self.compute_dtype, self.remat = dtype, compute_dtype, remat
        self.init_std, self.entropy_weight = init_std, entropy_weight
        self.input_shape = (self.sequence_length,)

    def _init(self) -> dict:
        return {"weight_init": WeightInit.DISTRIBUTION,
                "dist": {"type": "normal", "mean": 0.0, "std": self.init_std}}

    def _block(self, name: str, sublayer) -> PreNormResidual:
        return PreNormResidual(name=name, layer=sublayer,
                               eps=self.config["rms_norm_eps"], zero_centred=False,
                               sandwich=True, **self._init())

    def blocks(self) -> list:
        """The held layers' blocks in order: attention, then MLP, a layer."""
        c = self.config
        d, heads = c["hidden_size"], c["num_attention_heads"]
        out = []
        for i in range(self.layers):
            out.append(self._block(f"l{i}_attn", GatedAttention(
                n_in=d, n_out=d, n_heads=heads,
                n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                rotary_dim=c["head_dim"], rope_theta=float(c["rope_theta"]),
                eps=c["rms_norm_eps"], output_gate=False, qk_norm=False,
                **self._init())))
            out.append(self._block(f"l{i}_mlp", GatedMLP(
                n_in=d, n_out=d, width=c["intermediate_size"], **self._init())))
        return out

    def conf(self):
        c = self.config
        d = c["hidden_size"]
        g = (NeuralNetConfiguration.Builder().seed(self.seed).dtype(self.dtype)
             .compute_dtype(self.compute_dtype).remat(self.remat)
             .updater(self.updater).graph_builder().add_inputs("ids"))
        g.add_layer("embed", TokenEmbedding(n_in=c["vocab_size"], n_out=d,
                                            **self._init()), "ids")
        g.add_layer("loop", LoopedStack(blocks=self.blocks(),
                                        passes=int(c["total_ut_steps"]),
                                        eps=c["rms_norm_eps"]), "embed")
        g.add_layer("exit_head", LoopExitHead(
            n_in=d, n_out=c["vocab_size"], entropy_weight=self.entropy_weight,
            **self._init()), "loop")
        return g.set_outputs("exit_head").set_input_types(
            InputType.feed_forward(self.sequence_length)).build()

    def init(self):
        return ComputationGraph(self.conf()).init()
