"""Tensor- and pipeline-parallel training of REAL networks.

Takes the devices it finds and needs eight of them: the sharding logic is
the same on an 8-chip TPU slice (over ICI) and on eight virtual host
devices. On the slice:

  python examples/model_parallel_training.py

For the CPU demo, ask for the virtual devices from the environment:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/model_parallel_training.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deeplearning4j_tpu.common.enums import Activation, LossFunction
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.feedforward import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater.updaters import Adam
from deeplearning4j_tpu.parallel import (
    PipelinedTrainer, ShardedTrainer, make_mesh)


def data(n=64, n_in=12, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, n_in).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.randint(0, classes, n)]
    return x, y


def tensor_parallel_demo():
    """dp x tp: batch over 'data', Megatron-sharded weights over 'model'.
    GSPMD inserts every collective; works for any MultiLayerNetwork,
    ComputationGraph, or zoo model (e.g. ShardedTrainer over ResNet50)."""
    conf = (NeuralNetConfiguration.Builder().seed(7)
            .updater(Adam(learning_rate=1e-2)).list()
            .layer(DenseLayer(n_in=12, n_out=64, activation=Activation.TANH))
            .layer(DenseLayer(n_out=64, activation=Activation.RELU))
            .layer(OutputLayer(n_out=4, loss_fn=LossFunction.MCXENT))
            .set_input_type(InputType.feed_forward(12))
            .build())
    net = MultiLayerNetwork(conf).init()
    mesh = make_mesh(8, axes=("data", "model"), shape=(2, 4))
    st = ShardedTrainer.Builder(net).mesh(mesh).build()
    print("tp shard specs:", st.shard_specs())
    x, y = data()
    losses = st.fit_on_device(x, y, steps=50)
    print(f"tp loss: {losses[0]:.4f} -> {losses[-1]:.4f}")
    # the trained net is a normal network again: evaluate, serialize, ...
    print("output shape:", np.asarray(net.output(x)).shape)


def pipeline_parallel_demo():
    """GPipe microbatch pipeline over Mesh('pipe') for a homogeneous stack."""
    b = (NeuralNetConfiguration.Builder().seed(3)
         .updater(Adam(learning_rate=1e-2)).list()
         .layer(DenseLayer(n_in=12, n_out=32, activation=Activation.TANH)))
    for _ in range(4):
        b = b.layer(DenseLayer(n_out=32, activation=Activation.TANH))
    conf = (b.layer(OutputLayer(n_out=4, loss_fn=LossFunction.MCXENT))
            .set_input_type(InputType.feed_forward(12)).build())
    net = MultiLayerNetwork(conf).init()
    pt = (PipelinedTrainer.Builder(net)
          .mesh(make_mesh(4, axes=("pipe",)))
          .stage_range(1, 5)          # 4 identical Dense(32) stages
          .microbatches(4).build())
    x, y = data()
    losses = pt.fit_on_device(x, y, steps=50)
    print(f"pp loss: {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    tensor_parallel_demo()
    pipeline_parallel_demo()
