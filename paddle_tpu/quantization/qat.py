"""QAT/PTQ engines and quantized layers (reference
python/paddle/quantization/qat.py, ptq.py, quantize.py and
python/paddle/nn/quant/quant_layers.py). `convert` bakes observed scales
for inference — int8 simulation in bf16/fp32 compute, which is what the
MXU wants; `to_int8_inference` swaps in the Pallas quantized matmul.
"""
from __future__ import annotations

import copy

import numpy as np

import jax.numpy as jnp

from ..core.tensor import Tensor, unwrap
from ..nn.layer import Layer
from ..nn import functional as F
from .config import QuantConfig
from .observers import BaseObserver, BaseQuanter, quant_dequant


# ------------------------------------------------------- quantized layers

class QuantedLinear(Layer):
    """Linear with weight+activation fake quant (reference
    nn/quant/qat/linear.py QuantedLinear)."""

    def __init__(self, layer, q_config: SingleLayerConfig):
        super().__init__()
        self.weight = layer.weight
        self.bias = layer.bias
        self.activation_quanter = (
            q_config.activation._instance(layer)
            if q_config.activation else None)
        self.weight_quanter = (
            q_config.weight._instance(layer) if q_config.weight else None)

    def forward(self, x):
        w = self.weight
        if self.weight_quanter is not None:
            w = self.weight_quanter(w)
        if self.activation_quanter is not None:
            x = self.activation_quanter(x)
        return F.linear(x, w, self.bias)


class QuantedConv2D(Layer):
    def __init__(self, layer, q_config: SingleLayerConfig):
        super().__init__()
        self.weight = layer.weight
        self.bias = layer.bias
        # copy conv config as plain attrs: keeping `layer` as a sublayer
        # would leave the raw Conv2D visible to named_sublayers and let a
        # second quantize() pass double-wrap it
        self._stride = layer.stride
        self._padding = layer.padding
        self._dilation = layer.dilation
        self._groups = layer.groups
        self._data_format = layer.data_format
        self.activation_quanter = (
            q_config.activation._instance(layer)
            if q_config.activation else None)
        self.weight_quanter = (
            q_config.weight._instance(layer) if q_config.weight else None)

    def forward(self, x):
        w = self.weight
        if self.weight_quanter is not None:
            w = self.weight_quanter(w)
        if self.activation_quanter is not None:
            x = self.activation_quanter(x)
        return F.conv2d(x, w, self.bias, stride=self._stride,
                        padding=self._padding, dilation=self._dilation,
                        groups=self._groups, data_format=self._data_format)


def _default_qat_mapping():
    from ..nn.layers_basic import Linear
    mapping = {Linear: QuantedLinear}
    try:
        from ..nn.layers_basic import Conv2D
        mapping[Conv2D] = QuantedConv2D
    except ImportError:
        pass
    return mapping


_DEFAULT_QAT_MAPPING = _default_qat_mapping()


# ---------------------------------------------------------------- engines

class Quantization:
    def __init__(self, config: QuantConfig):
        self._config = config

    def _transform(self, model, wrap_fn, inplace=False):
        if not inplace:
            import copy
            model = copy.deepcopy(model)  # keep the fp original intact
        for name, sub in list(model.named_sublayers()):
            cfg = self._config._config_for(sub, name)
            target = self._config._qat_mapping.get(type(sub))
            if cfg is not None and target is not None:
                replacement = wrap_fn(sub, cfg, target)
                _set_sublayer(model, name, replacement)
        return model

    def quantize(self, model, inplace=False):
        return self._transform(model,
                               lambda sub, cfg, tgt: tgt(sub, cfg),
                               inplace=inplace)

    def convert(self, model, inplace=False):
        """Freeze: eval-mode scales baked; observers stop updating. With
        inplace=False (default) the QAT/calibration model stays live and a
        frozen copy is returned."""
        if not inplace:
            import copy
            model = copy.deepcopy(model)
        model.eval()
        for _, sub in model.named_sublayers(include_self=True):
            if isinstance(sub, BaseObserver):
                sub._frozen = True
        return model


class QAT(Quantization):
    """Quantization-aware training (reference qat.py). quantize() swaps
    matched layers for Quanted* wrappers with trainable-through STE."""


class PTQ(Quantization):
    """Post-training quantization (reference ptq.py): wrap with observers,
    run calibration batches, then convert()."""


def _set_sublayer(root, dotted, new):
    parts = dotted.split(".")
    obj = root
    for p in parts[:-1]:
        obj = getattr(obj, p)
    setattr(obj, parts[-1], new)


class Int8InferLinear(Layer):
    """True-int8 inference Linear (reference capability: the cutlass int8
    deploy kernels behind PTQ convert). Weights pre-quantized to int8 with
    per-output-channel scales; forward runs the Pallas int8 MXU matmul
    (ops/pallas/quant_matmul.py) with activation quantization per batch
    and fused dequantize."""

    def __init__(self, layer):
        super().__init__()
        import jax.numpy as jnp

        from ..core.tensor import unwrap, wrap
        from ..ops.pallas.quant_matmul import quantize_tensor
        w = unwrap(layer.weight)
        qw, sw = quantize_tensor(w, per_channel_axis=1)
        self.register_buffer("qweight", wrap(qw))
        self.register_buffer("w_scale", wrap(jnp.asarray(sw)))
        self.bias = getattr(layer, "bias", None)

    def forward(self, x):
        from ..core.tensor import dispatch
        from ..ops.pallas import quant_matmul as qm

        def fn(xv, qw, sw):
            import jax
            # deploy-only path: int8 rounding is non-differentiable and the
            # Pallas kernel has no JVP rule — cut the tangent explicitly
            xv = jax.lax.stop_gradient(xv)
            shape = xv.shape
            x2 = xv.reshape(-1, shape[-1])
            qx, sx = qm.quantize_tensor(x2)
            out = qm.quantized_matmul(qx, qw, sx, sw)
            return out.reshape(shape[:-1] + (out.shape[-1],)).astype(
                xv.dtype)

        out = dispatch(fn, x, self.qweight, self.w_scale,
                       nondiff_args=(1, 2), name="int8_linear")
        if self.bias is not None:
            out = out + self.bias
        return out


def to_int8_inference(model, inplace=False):
    """Replace (Quanted)Linear layers with true-int8 Int8InferLinear for
    deployment (the step after convert(); reference: save_quantized_model
    emitting int8 ops)."""
    if not inplace:
        import copy
        model = copy.deepcopy(model)
    for name, sub in list(model.named_sublayers()):
        from ..nn.layers_basic import Linear
        if isinstance(sub, (Linear, QuantedLinear)):
            _set_sublayer(model, name, Int8InferLinear(sub))
    return model


