"""ComputationGraph configuration + GraphBuilder.

Parity: ref nn/conf/ComputationGraphConfiguration.java (833 LoC, GraphBuilder) —
addInputs/addLayer/addVertex/setOutputs/setInputTypes, JSON round-trip, topological sort
at config time (ref ComputationGraph.java:393 topologicalSortOrder — here the sort lives
in the config because execution is a trace, not an interpreter).
"""
from __future__ import annotations

import copy
import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

from deeplearning4j_tpu.common.enums import BackpropType
from deeplearning4j_tpu.nn.conf.configuration import (
    _EXPECTED_KIND, GlobalConf, NeuralNetConfiguration, make_preprocessor)
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.base import BaseLayerConf
from deeplearning4j_tpu.nn.conf.preprocessors import InputPreProcessor
from deeplearning4j_tpu.nn.graph.vertices import GraphVertex


@dataclass
class GraphNode:
    name: str
    kind: str  # "layer" | "vertex"
    conf: Union[BaseLayerConf, GraphVertex]
    inputs: List[str]
    preprocessor: Optional[InputPreProcessor] = None
    # a layer node that uses another layer node's parameters (an output head
    # tied to another, a second reader of one token table): it holds none of
    # its own, and the gradient of both uses reaches the one copy
    tied_to: Optional[str] = None

    def to_dict(self):
        d = {
            "name": self.name, "kind": self.kind, "conf": self.conf.to_dict(),
            "inputs": list(self.inputs),
            "preprocessor": self.preprocessor.to_dict() if self.preprocessor else None,
        }
        if self.tied_to is not None:
            d["tied_to"] = self.tied_to
        return d

    @staticmethod
    def from_dict(d):
        kind = d["kind"]
        conf = (BaseLayerConf.from_dict(d["conf"]) if kind == "layer"
                else GraphVertex.from_dict(d["conf"]))
        pp = InputPreProcessor.from_dict(d["preprocessor"]) if d.get("preprocessor") else None
        return GraphNode(d["name"], kind, conf, list(d["inputs"]), pp,
                         d.get("tied_to"))


class ComputationGraphConfiguration:
    def __init__(self, inputs: List[str], outputs: List[str],
                 nodes: Dict[str, GraphNode], global_conf: GlobalConf,
                 input_types: Optional[List[InputType]] = None,
                 backprop_type: BackpropType = BackpropType.Standard,
                 tbptt_fwd_length: int = 20, tbptt_back_length: int = 20):
        self.inputs = inputs
        self.outputs = outputs
        self.nodes = nodes
        self.global_conf = global_conf
        self.input_types = input_types
        self.backprop_type = backprop_type
        self.tbptt_fwd_length = tbptt_fwd_length
        self.tbptt_back_length = tbptt_back_length
        self.topo_order = self._topological_sort()

    # ---- topo sort (ref ComputationGraph.java:393/:1172) ----
    def _topological_sort(self) -> List[str]:
        indeg = {n: 0 for n in self.nodes}
        dependents: Dict[str, List[str]] = {n: [] for n in self.nodes}
        for name, node in self.nodes.items():
            for inp in node.inputs:
                if inp in self.nodes:
                    indeg[name] += 1
                    dependents[inp].append(name)
                elif inp not in self.inputs:
                    raise ValueError(f"Node '{name}' references unknown input '{inp}'")
        from collections import deque
        ready = deque(sorted(n for n, d in indeg.items() if d == 0))
        order = []
        while ready:
            n = ready.popleft()
            order.append(n)
            for dep in dependents[n]:
                indeg[dep] -= 1
                if indeg[dep] == 0:
                    ready.append(dep)
        if len(order) != len(self.nodes):
            cyc = set(self.nodes) - set(order)
            raise ValueError(f"Graph contains a cycle involving: {sorted(cyc)}")
        return order

    # ---- shape inference over the DAG ----
    def node_input_types(self) -> Dict[str, List[InputType]]:
        """InputTypes flowing *into* each node (post-preprocessor for layers)."""
        if self.input_types is None:
            raise ValueError("Configuration has no input types set")
        known: Dict[str, InputType] = dict(zip(self.inputs, self.input_types))
        result: Dict[str, List[InputType]] = {}
        for name in self.topo_order:
            node = self.nodes[name]
            in_types = [known[i] for i in node.inputs]
            if node.kind == "layer":
                if node.preprocessor is not None:
                    in_types = [node.preprocessor.get_output_type(in_types[0])]
                result[name] = in_types
                known[name] = node.conf.get_output_type(in_types[0])
            else:
                result[name] = in_types
                known[name] = node.conf.get_output_type(in_types)
        return result

    # ---- serde ----
    def to_dict(self):
        return {
            "inputs": list(self.inputs), "outputs": list(self.outputs),
            "nodes": {k: v.to_dict() for k, v in self.nodes.items()},
            "global_conf": self.global_conf.to_dict(),
            "input_types": [t.to_dict() for t in self.input_types]
            if self.input_types else None,
            "backprop_type": self.backprop_type.value,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            "network_type": "ComputationGraph",
        }

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), indent=kw.pop("indent", 2), **kw)

    @staticmethod
    def from_dict(d):
        return ComputationGraphConfiguration(
            inputs=list(d["inputs"]), outputs=list(d["outputs"]),
            nodes={k: GraphNode.from_dict(v) for k, v in d["nodes"].items()},
            global_conf=GlobalConf.from_dict(d["global_conf"]),
            input_types=[InputType.from_dict(t) for t in d["input_types"]]
            if d.get("input_types") else None,
            backprop_type=BackpropType(d.get("backprop_type", "standard")),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20))

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration.from_dict(json.loads(s))

    def to_yaml(self) -> str:
        """(ref ComputationGraphConfiguration.toYaml)"""
        import yaml
        return yaml.safe_dump(json.loads(self.to_json()), sort_keys=False)

    @staticmethod
    def from_yaml(s: str) -> "ComputationGraphConfiguration":
        import yaml
        return ComputationGraphConfiguration.from_dict(yaml.safe_load(s))
    toYaml = to_yaml
    fromYaml = from_yaml

    def get_updater(self):
        from deeplearning4j_tpu.nn.updater.updaters import BaseUpdater, Sgd
        if self.global_conf.updater is None:
            return Sgd()
        return BaseUpdater.from_dict(self.global_conf.updater)


class GraphBuilder:
    """ref ComputationGraphConfiguration.GraphBuilder (via
    NeuralNetConfiguration.Builder().graphBuilder())."""

    def __init__(self, parent: "NeuralNetConfiguration.Builder"):
        self._parent = parent
        self._inputs: List[str] = []
        self._outputs: List[str] = []
        self._nodes: Dict[str, GraphNode] = {}
        self._input_types: Optional[List[InputType]] = None
        self._backprop_type = BackpropType.Standard
        self._tbptt_fwd = 20
        self._tbptt_back = 20

    def add_inputs(self, *names: str):
        self._inputs.extend(names)
        return self
    addInputs = add_inputs

    def add_layer(self, name: str, layer: BaseLayerConf, *inputs: str,
                  preprocessor: Optional[InputPreProcessor] = None,
                  tied_to: Optional[str] = None):
        """`tied_to` names the layer node whose parameters this one uses
        in place of its own (same layer shapes)."""
        if name in self._nodes or name in self._inputs:
            raise ValueError(f"Duplicate node name '{name}'")
        layer = self._parent._apply_defaults(layer)
        layer.name = name
        self._nodes[name] = GraphNode(name, "layer", layer, list(inputs),
                                      preprocessor, tied_to)
        return self
    addLayer = add_layer

    def add_vertex(self, name: str, vertex: GraphVertex, *inputs: str):
        if name in self._nodes or name in self._inputs:
            raise ValueError(f"Duplicate node name '{name}'")
        self._nodes[name] = GraphNode(name, "vertex", vertex, list(inputs))
        return self
    addVertex = add_vertex

    def set_outputs(self, *names: str):
        self._outputs = list(names)
        return self
    setOutputs = set_outputs

    def set_input_types(self, *types: InputType):
        self._input_types = list(types)
        return self
    setInputTypes = set_input_types

    def backprop_type(self, t: BackpropType):
        self._backprop_type = t
        return self

    def t_bptt_forward_length(self, n: int):
        self._tbptt_fwd = int(n)
        return self

    def t_bptt_backward_length(self, n: int):
        self._tbptt_back = int(n)
        return self

    def pretrain(self, b: bool):
        return self

    def backprop(self, b: bool):
        return self

    def build(self) -> ComputationGraphConfiguration:
        conf = ComputationGraphConfiguration(
            inputs=list(self._inputs), outputs=list(self._outputs),
            nodes=self._nodes, global_conf=copy.deepcopy(self._parent._global),
            input_types=self._input_types, backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd, tbptt_back_length=self._tbptt_back)

        gc = conf.global_conf
        if gc.updater is None and "learning_rate" in self._parent._layer_defaults:
            from deeplearning4j_tpu.nn.updater.updaters import Sgd
            gc.updater = Sgd(
                learning_rate=self._parent._layer_defaults["learning_rate"]).to_dict()

        for out in conf.outputs:
            if out not in conf.nodes:
                raise ValueError(f"Output '{out}' is not a node in the graph")
        for name, node in conf.nodes.items():
            owner = conf.nodes.get(node.tied_to) if node.tied_to else None
            if node.tied_to and (owner is None or owner.kind != "layer"
                                 or owner.tied_to is not None):
                raise ValueError(f"Node '{name}' is tied to '{node.tied_to}', "
                                 "which is no layer node with parameters of its own")

        if conf.input_types is not None:
            if len(conf.input_types) != len(conf.inputs):
                raise ValueError("setInputTypes count must match addInputs count")
            # two passes like ListBuilder: auto preprocessors + nIn inference, in topo order
            known: Dict[str, InputType] = dict(zip(conf.inputs, conf.input_types))
            for name in conf.topo_order:
                node = conf.nodes[name]
                in_types = [known[i] for i in node.inputs]
                if node.kind == "layer":
                    cur = in_types[0]
                    expected = _EXPECTED_KIND.get(type(node.conf).__name__)
                    if node.preprocessor is None and expected is not None:
                        node.preprocessor = make_preprocessor(cur, expected)
                    if node.preprocessor is not None:
                        cur = node.preprocessor.get_output_type(cur)
                    node.conf.set_n_in(cur, override=False)
                    known[name] = node.conf.get_output_type(cur)
                else:
                    known[name] = node.conf.get_output_type(in_types)
        return conf
