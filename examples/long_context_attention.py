"""Long-context attention: blockwise (flash-recurrence) + ring context
parallelism.

Demonstrates the two long-context paths of SelfAttentionLayer:
- single device: T far beyond the dense O(T^2) score tensor's memory, via
  the online-softmax block scan (layer default past `block_size`);
- 8-device mesh: the time dimension sharded over a 'seq' axis, with either
  GSPMD-partitioned dense einsums or the hand-scheduled ring (k/v blocks
  rotating via ppermute).

Takes the devices it finds and needs eight of them. On an 8-chip slice:

  python examples/long_context_attention.py

For the CPU demo, ask for eight virtual host devices from the environment:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/long_context_attention.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deeplearning4j_tpu.common.enums import Activation, LossFunction
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.conf.layers.recurrent import RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater.updaters import Adam
from deeplearning4j_tpu.parallel import ShardedTrainer, make_mesh


def build(seed=7):
    conf = (NeuralNetConfiguration.Builder().seed(seed).dtype("float32")
            .updater(Adam(learning_rate=1e-3)).list()
            .layer(SelfAttentionLayer(n_in=32, n_out=32, n_heads=4,
                                      causal=True, block_size=128))
            .layer(RnnOutputLayer(n_out=8, loss_fn=LossFunction.MCXENT,
                                  activation=Activation.SOFTMAX))
            .set_input_type(InputType.recurrent(32))
            .build())
    return MultiLayerNetwork(conf).init()


def data(b, t, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(b, 32, t).astype(np.float32)
    y = np.eye(8, dtype=np.float32)[rng.randint(0, 8, (b, t))]
    return x, y.transpose(0, 2, 1)


def main():
    # 1. single-device long context: T=1024 -> the dense (B,H,T,T) scores
    #    would be 4*4*1024^2*4B = 64 MB *per example dim pair*; the block
    #    scan keeps peak activation memory O(T * block)
    net = build()
    x, y = data(b=2, t=1024)
    losses = net.fit_on_device(x, y, steps=3)
    print(f"blockwise T=1024 losses: {np.asarray(losses)}")

    # 2. context parallelism: shard the time axis over 4 of 8 devices
    #    (2-way data parallel x 4-way sequence parallel)
    mesh = make_mesh(8, axes=("data", "seq"), shape=(2, 4))
    x, y = data(b=4, t=64, seed=1)

    st = (ShardedTrainer.Builder(build()).mesh(mesh)
          .sequence_axis("seq").build())           # GSPMD partitions einsums
    print("GSPMD CP losses:", np.asarray(st.fit_on_device(x, y, steps=2)))

    st_ring = (ShardedTrainer.Builder(build()).mesh(mesh)
               .sequence_axis("seq").ring_attention(True).build())
    print("ring CP losses :", np.asarray(st_ring.fit_on_device(x, y, steps=2)))


if __name__ == "__main__":
    main()
