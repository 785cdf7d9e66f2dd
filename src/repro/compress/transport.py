"""Codec-agnostic transport layer for the FL links (DESIGN.md §Transport).

One :class:`Codec` interface unifies the three faces every lossy link has:

  * ``lossy(params)``    — the in-graph quantize-dequantize step that models
    the link's effect on learning dynamics inside a jitted train path;
  * ``marshal/unmarshal`` — the actual wire message (what would be sent);
  * ``payload_bytes``     — wire-size accounting for the byte metrics.

Registered codecs:

  ``none``         identity links, raw f32 accounting.
  ``polyline``     the paper's §4.3 Encoded Polyline Algorithm codec
                   (``polyline:<p>`` selects the precision, default 4).
  ``quantize8``    blockwise fixed-point int8 quantization — the TPU-native
  ``quantize16``   polyline analogue (DESIGN.md §Hardware-adaptation).  The
                   lossy step runs the Pallas kernel in
                   kernels/polyline_codec.py (interpret mode on CPU).

``measure_ratio`` estimates wire/raw bytes on a size-capped parameter
sample so byte accounting stays cheap at scale (see the note on the
accounting approximation below).
"""
from __future__ import annotations

import abc
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress import polyline, quantize

#: default element cap for sampled wire-ratio measurement.  Accounting
#: approximation: the ratio is measured on a per-leaf-proportional prefix
#: sample of at most this many elements and applied to the full model's
#: byte count.  Polyline payload length depends only on the local value
#: distribution (delta magnitudes), which the sample preserves; models
#: smaller than the cap are measured exactly.
RATIO_SAMPLE_ELEMS = 65536


def _sample_tree(params: Any, max_elems: Optional[int]) -> List[np.ndarray]:
    """Per-leaf-proportional flat prefix sample of a pytree (a list of 1-D
    arrays is itself a pytree, so codecs can marshal it directly)."""
    leaves = [np.asarray(l).reshape(-1) for l in jax.tree.leaves(params)]
    total = sum(l.size for l in leaves)
    if max_elems is None or total <= max_elems:
        return leaves
    frac = max_elems / total
    return [l[:max(1, int(l.size * frac))] for l in leaves]


class Codec(abc.ABC):
    """A lossy (or identity) link codec; see module docstring."""

    name: str = "codec"
    #: whether ``lossy`` is jit-composable (pure jax ops / Pallas), i.e.
    #: can run inside the fused round step (core/executor.py).  All
    #: registered codecs are in-graph: ``none`` is the identity,
    #: ``polyline`` rounds with jnp ops, ``quantize*`` runs the Pallas
    #: kernel.  A future host-side codec (e.g. one marshalling through
    #: Python bytes) must set this False and will be rejected by the
    #: fused step with a clear error.
    in_graph: bool = True

    def lossy(self, params: Any) -> Any:
        """In-graph encode->decode roundtrip (models the link's loss)."""
        return params

    @abc.abstractmethod
    def marshal(self, params: Any) -> Dict[str, Any]:
        """Pytree -> wire message."""

    @abc.abstractmethod
    def unmarshal(self, msg: Dict[str, Any]) -> Any:
        """Wire message -> pytree."""

    @abc.abstractmethod
    def payload_bytes(self, msg: Dict[str, Any]) -> int:
        """Wire size of a marshalled message."""

    def fixed_overhead_bytes(self, msg: Dict[str, Any]) -> int:
        """Per-leaf fixed wire costs (metadata) inside ``payload_bytes`` —
        charged once per leaf regardless of how much of it was sampled."""
        return 0

    def measure_ratio(self, params: Any,
                      max_elems: Optional[int] = RATIO_SAMPLE_ELEMS) -> float:
        """Wire bytes / raw f32 bytes, measured on a capped sample.

        The variable (per-value) payload rate is extrapolated from the
        sample; per-leaf fixed costs are added once, so many-leaf models
        are not biased by sampling.  Exact when the model fits the cap.
        """
        sample = _sample_tree(params, max_elems)
        msg = self.marshal(sample)
        overhead = self.fixed_overhead_bytes(msg)
        raw_sample = polyline.raw_bytes(sample)
        raw_full = polyline.raw_bytes(params)
        var_rate = (self.payload_bytes(msg) - overhead) / raw_sample
        return (var_rate * raw_full + overhead) / raw_full


class NoneCodec(Codec):
    """Uncompressed f32 links (the baselines' Table 2 setting)."""

    name = "none"

    def marshal(self, params):
        leaves, treedef = jax.tree.flatten(params)
        return {"leaves": [np.asarray(l) for l in leaves],
                "treedef": treedef}

    def unmarshal(self, msg):
        return jax.tree.unflatten(msg["treedef"], msg["leaves"])

    def payload_bytes(self, msg):
        return sum(l.nbytes for l in msg["leaves"])

    def measure_ratio(self, params, max_elems=RATIO_SAMPLE_ELEMS):
        return 1.0


class PolylineCodec(Codec):
    """The paper's reference compressor (compress/polyline.py)."""

    def __init__(self, precision: int = 4):
        self.precision = precision
        self.name = f"polyline:{precision}"

    def lossy(self, params):
        # the codec's exact lossy step: round to `precision` decimals.
        # Written as multiply-by-reciprocal, not division: XLA rewrites
        # x / const to x * (1/const) inside fused programs but not in
        # op-by-op dispatch, so the division form is not bitwise
        # reproducible between eager and jitted execution (the fused
        # round step requires eager == in-graph, core/executor.py).
        f = np.float32(10.0 ** self.precision)
        inv = np.float32(1.0 / (10.0 ** self.precision))
        return jax.tree.map(lambda x: jnp.round(x * f) * inv, params)

    def marshal(self, params):
        return polyline.marshal(params, self.precision)

    def unmarshal(self, msg):
        return polyline.unmarshal(msg)

    def payload_bytes(self, msg):
        return polyline.payload_bytes(msg)

    def fixed_overhead_bytes(self, msg):
        return 8 * len(msg["shapes"])  # dims metadata per leaf


class QuantizeCodec(Codec):
    """Blockwise fixed-point quantization, Pallas-kernel lossy step.

    Wire format and byte accounting come from compress/quantize.py; the
    in-graph roundtrip runs the TPU kernel in kernels/polyline_codec.py.
    ``interpret=None`` leaves the choice to ``kernels.ops.interpret_mode``
    (compiled on a TPU); tests pass ``True``.
    """

    def __init__(self, bits: int = 8, interpret: Optional[bool] = None):
        if not 2 <= bits <= 16:
            # the wire dtype is int8/int16; wider widths would silently
            # wrap when q is cast (quantize.compress)
            raise ValueError(f"quantize codec supports 2..16 bits, got {bits}")
        self.bits = bits
        self.interpret = interpret
        self.name = f"quantize{bits}"

    def lossy(self, params):
        from repro.kernels import ops  # lazy: keeps transport import light

        def roundtrip(x):
            q, scale = ops.compress(x, self.bits, interpret=self.interpret)
            return ops.decompress(q, scale, x.shape,
                                  interpret=self.interpret).astype(x.dtype)
        return jax.tree.map(roundtrip, params)

    def marshal(self, params):
        return quantize.compress_tree(params, self.bits)

    def unmarshal(self, msg):
        return quantize.decompress_tree(msg)

    def payload_bytes(self, msg):
        return quantize.tree_wire_bytes(msg)

    def measure_ratio(self, params, max_elems=RATIO_SAMPLE_ELEMS):
        # exact and cheap: the wire size depends only on leaf sizes
        # (ceil(n/256) blocks of 256*itemsize + 4 scale bytes, + 8
        # metadata bytes per leaf), never on the values
        itemsize = 1 if self.bits <= 8 else 2
        leaves = [np.asarray(l) for l in jax.tree.leaves(params)]
        wire = sum(-(-l.size // quantize.BLOCK)
                   * (quantize.BLOCK * itemsize + 4) for l in leaves)
        wire += 8 * len(leaves)
        return wire / polyline.raw_bytes(leaves)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[..., Codec]] = {}


def register_codec(name: str, factory: Callable[..., Codec]) -> None:
    _REGISTRY[name] = factory


def registered_codecs() -> List[str]:
    """Registered codec family names (the api layer's validation surface)."""
    return sorted(_REGISTRY)


register_codec("none", lambda: NoneCodec())
register_codec("polyline", lambda p=4: PolylineCodec(int(p)))
register_codec("quantize", lambda b=8: QuantizeCodec(int(b)))
register_codec("quantize8", lambda: QuantizeCodec(8))
register_codec("quantize16", lambda: QuantizeCodec(16))


def get_codec(spec: Union[str, Codec, None]) -> Codec:
    """Resolve ``'polyline'``, ``'polyline:6'``, ``'quantize8'``, a Codec
    instance, or None (identity) to a Codec."""
    if spec is None:
        return NoneCodec()
    if isinstance(spec, Codec):
        return spec
    name, _, arg = str(spec).partition(":")
    if name not in _REGISTRY:
        raise ValueError(f"unknown codec {spec!r}; "
                         f"registered: {sorted(_REGISTRY)}")
    if not arg:
        return _REGISTRY[name]()
    try:
        return _REGISTRY[name](arg)
    except (TypeError, ValueError) as e:
        raise ValueError(f"bad argument in codec spec {spec!r} "
                         f"(expected e.g. 'polyline:4', 'quantize:16'): {e}")


def cross_tier_bits(spec: Union[str, Codec]) -> int:
    """Int width for the in-SPMD cross-tier collective (core/steps.py).

    Only the quantize family can ride inside a jitted collective; polyline
    is a host-side wire codec.
    """
    codec = get_codec(spec)
    if not isinstance(codec, QuantizeCodec):
        raise ValueError(
            f"codec {codec.name!r} cannot run inside the cross-tier "
            "collective; use quantize8/quantize16")
    return codec.bits
