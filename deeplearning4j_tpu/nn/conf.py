"""Network configuration DSL.

Reference parity: org.deeplearning4j.nn.conf.NeuralNetConfiguration
(builder + Jackson JSON serde) and MultiLayerConfiguration. The builder
shape follows the reference —

    conf = (NeuralNetConfiguration.builder()
            .seed(123)
            .updater(Adam(1e-3))
            .l2(1e-4)
            .list()
            .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5)))
            .layer(SubsamplingLayer(kernel_size=(2, 2)))
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_out=10, loss_function="MCXENT"))
            .set_input_type(InputType.convolutional(28, 28, 1))
            .build())

— but the built artifact compiles to one SameDiff graph rather than a stack
of imperative layer objects (there is no second execution path; the
reference's nn/layers/samediff bridge is the *only* path here).
"""
from __future__ import annotations

import dataclasses
import json
from typing import List, Optional, Sequence

from deeplearning4j_tpu.autodiff.training import MixedPrecision
from deeplearning4j_tpu.learning.updaters import IUpdater, Sgd
from deeplearning4j_tpu.learning.regularization import (
    L1Regularization, L2Regularization, Regularization, WeightDecay)
from deeplearning4j_tpu.nn.layers import BaseLayer, InputType


@dataclasses.dataclass
class MultiLayerConfiguration:
    layers: List[BaseLayer]
    input_type: InputType
    seed: int = 12345
    updater: IUpdater = dataclasses.field(default_factory=lambda: Sgd(0.01))
    regularization: Sequence[Regularization] = ()
    dtype: str = "float32"
    grad_clip_value: Optional[float] = None
    mixed_precision: Optional[MixedPrecision] = None
    # internal cnn tensor layout; "NHWC" is TPU-native (logical NCHW
    # costs a physical transpose per conv). External API stays NCHW either way.
    # from_json defaults to "NCHW" so checkpoints saved before this field
    # existed keep their trained flatten-order weights valid.
    cnn_data_format: str = "NHWC"
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0

    # --- serde (reference: MultiLayerConfiguration.toJson/fromJson) -----
    def to_json(self) -> str:
        return json.dumps({
            "seed": self.seed,
            "dtype": self.dtype,
            "cnn_data_format": self.cnn_data_format,
            "grad_clip_value": self.grad_clip_value,
            "mixed_precision": (self.mixed_precision.to_json()
                                if self.mixed_precision else None),
            "gradient_normalization": self.gradient_normalization,
            "gradient_normalization_threshold":
                self.gradient_normalization_threshold,
            "updater": self.updater.to_json(),
            "regularization": [r.to_json() for r in self.regularization],
            "input_type": self.input_type.to_json(),
            "layers": [l.to_json() for l in self.layers],
        }, indent=1)

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        d = json.loads(s)
        return MultiLayerConfiguration(
            layers=[BaseLayer.from_json(ld) for ld in d["layers"]],
            input_type=InputType.from_json(d["input_type"]),
            seed=d.get("seed", 12345),
            updater=IUpdater.from_json(d["updater"]),
            regularization=[Regularization.from_json(r)
                            for r in d.get("regularization", [])],
            dtype=d.get("dtype", "float32"),
            cnn_data_format=d.get("cnn_data_format", "NCHW"),
            grad_clip_value=d.get("grad_clip_value"),
            mixed_precision=MixedPrecision.from_json(d.get("mixed_precision")),
            gradient_normalization=d.get("gradient_normalization"),
            gradient_normalization_threshold=d.get(
                "gradient_normalization_threshold", 1.0),
        )


class ListBuilder:
    def __init__(self, parent: "NeuralNetConfiguration.Builder"):
        self._parent = parent
        self._layers: List[BaseLayer] = []
        self._input_type: Optional[InputType] = None

    def layer(self, layer: BaseLayer) -> "ListBuilder":
        self._layers.append(layer)
        return self

    def set_input_type(self, itype: InputType) -> "ListBuilder":
        self._input_type = itype
        return self

    def build(self) -> MultiLayerConfiguration:
        if self._input_type is None:
            raise ValueError("set_input_type(...) is required (the reference "
                             "infers nIn via setInputType the same way)")
        p = self._parent
        regs: List[Regularization] = []
        if p._l1:
            regs.append(L1Regularization(l1=p._l1))
        if p._l2:
            regs.append(L2Regularization(l2=p._l2))
        if p._weight_decay:
            regs.append(WeightDecay(coeff=p._weight_decay))
        return MultiLayerConfiguration(
            layers=self._layers, input_type=self._input_type, seed=p._seed,
            updater=p._updater, regularization=regs, dtype=p._dtype,
            grad_clip_value=p._grad_clip, mixed_precision=p._mixed_precision,
            gradient_normalization=p._grad_norm,
            gradient_normalization_threshold=p._grad_norm_threshold)


class NeuralNetConfiguration:
    class Builder:
        def __init__(self):
            self._seed = 12345
            self._updater: IUpdater = Sgd(0.01)
            self._l1 = 0.0
            self._l2 = 0.0
            self._weight_decay = 0.0
            self._dtype = "float32"
            self._grad_clip = None
            self._mixed_precision = None
            self._grad_norm = None
            self._grad_norm_threshold = 1.0

        def seed(self, s: int):            self._seed = int(s); return self
        def updater(self, u: IUpdater):    self._updater = u; return self
        def l1(self, v: float):            self._l1 = v; return self
        def l2(self, v: float):            self._l2 = v; return self
        def weight_decay(self, v: float):  self._weight_decay = v; return self
        def data_type(self, dt: str):      self._dtype = dt; return self
        def gradient_clip(self, v: float): self._grad_clip = v; return self

        def mixed_precision(self, mp=True):
            """bf16-compute / f32-master-param training policy (pass a
            MixedPrecision for a custom compute dtype / loss scale)."""
            self._mixed_precision = MixedPrecision() if mp is True else mp
            return self

        def gradient_normalization(self, mode: str, threshold: float = 1.0):
            """clip_l2_per_layer | clip_l2_global | renormalize_l2_per_layer
            | clip_element_wise_absolute_value (reference:
            GradientNormalization enum, BaseMultiLayerUpdater.preApply)."""
            self._grad_norm = mode
            self._grad_norm_threshold = threshold
            return self

        def list(self) -> ListBuilder:
            return ListBuilder(self)

        def graph_builder(self):
            """DAG configuration (reference:
            NeuralNetConfiguration.Builder().graphBuilder())."""
            from deeplearning4j_tpu.nn.graph import GraphBuilder
            return GraphBuilder(self)

    @staticmethod
    def builder() -> "NeuralNetConfiguration.Builder":
        return NeuralNetConfiguration.Builder()
