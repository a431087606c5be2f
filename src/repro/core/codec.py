"""Codec registry — the node vocabulary of the graph model (paper §III-B, §V-A).

A codec is a reversible pair ``(encode, decode)`` over tuples of streams.  The
contract that makes the *universal decoder* possible (paper §III-D):

  * ``encode(streams, params) -> (out_streams, header)`` — ``params`` may shape
    the encoding arbitrarily.
  * ``decode(out_streams, header) -> streams`` — **parameter-free**: everything
    decode needs must be in the (per-node, wire-stored) ``header`` bytes.

Codec ids are wire-stable; ``min_version`` implements the paper's codec-by-codec
format-version gating (§V-C).

Backends
--------
The *encode* side of a codec may additionally be implemented per execution
backend (``register_backend_codec``).  The engine's ``execute`` phase asks the
selected backend for an implementation of each resolved node; when one is
registered and its ``applies`` predicate accepts the concrete streams, it is
used — otherwise execution falls back to the host encoder.  Backend encoders
must be bit-exact with the host encoder (same output streams, same header);
decode is always the host (universal-decoder) path.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.device import span
from repro.reliability.faults import fault_point

from .message import Stream

__all__ = [
    "Atom",
    "InPort",
    "ParamSpec",
    "CodecSig",
    "ANY_STYPES",
    "FIXED_STYPES",
    "BYTE_STYPES",
    "NUMERIC_WIDTHS",
    "CodecSpec",
    "register_codec",
    "get_codec",
    "get_codec_by_id",
    "all_codecs",
    "BackendCodecImpl",
    "register_backend_codec",
    "get_backend_codec",
    "available_backends",
    "run_encode_via",
]

EncodeFn = Callable[..., Tuple[List[Stream], bytes]]
DecodeFn = Callable[[Sequence[Stream], bytes], List[Stream]]


# ------------------------------------------------------- stream-type signatures
#
# The static contract of a codec over the stream-type lattice (paper §III-C:
# edges are *typed*).  An ``Atom`` is one point of the lattice: ``(stype,
# width)`` with ``width is None`` meaning "any width legal for that stype".
# Signatures are declarative data + one pure transfer function, which lets
# ``repro.analysis`` abstractly interpret whole plans before a byte is
# compressed, and lets the conformance fuzz suite tie every declaration to the
# encoder's real acceptance behavior.

Atom = Tuple[int, Optional[int]]  # (int(SType), width-or-None)

# SType values, spelled as ints so signature declarations stay cheap to import:
# SERIAL=0, STRUCT=1, NUMERIC=2, STRING=3 (see core.message.SType).
ANY_STYPES = frozenset((0, 1, 2, 3))
FIXED_STYPES = frozenset((0, 1, 2))  # everything except STRING
BYTE_STYPES = frozenset((0,))  # SERIAL only
NUMERIC_WIDTHS = frozenset((1, 2, 4, 8))


@dataclass(frozen=True)
class InPort:
    """Acceptance constraint for one codec input edge.

    ``widths is None`` accepts any width legal for the stype; otherwise the
    concrete width must be in the set (an unknown width *may* match — the
    analyzer only reports definite errors).
    """

    stypes: frozenset
    widths: Optional[frozenset] = None

    def accepts(self, atom: Atom) -> bool:
        st, w = atom
        if st not in self.stypes:
            return False
        if self.widths is not None and w is not None and w not in self.widths:
            return False
        return True


@dataclass(frozen=True)
class ParamSpec:
    """Schema entry for one codec parameter (documentation + lint surface)."""

    name: str
    kind: str  # "int" | "int_list" | "str" | "float"
    required: bool = False
    choices: Optional[tuple] = None
    doc: str = ""


@dataclass(frozen=True)
class CodecSig:
    """Declared stream-type signature of a codec.

    * ``inputs`` — one ``InPort`` per declared input; for variadic codecs
      (``n_inputs == -1``) a single port applied to every wired input.
    * ``transfer(atoms, params, n_out)`` — the abstract output function: given
      one concrete ``Atom`` per input (widths may be ``None`` = unknown) plus
      the node's params and declared output count, return the list of output
      atoms, or ``None`` when the encoder would reject this combination (the
      place for cross-input constraints — concat's "all same type", adj_gap's
      equal widths — and params/width consistency like float_split's fmt).
      Must be pure and total (never raise).
    * ``params`` — declared parameter schema.
    * ``expansion`` — worst-case output-bytes/input-bytes bound across all
      outputs combined (drives the per-terminal-edge expansion diagnostic).
    * ``packed_outputs`` — output indices carrying entropy-packed (already
      incompressible) bytes; feeding them onward is flagged by the linter.
    """

    inputs: Tuple[InPort, ...]
    transfer: Callable[[Tuple[Atom, ...], dict, int], Optional[List[Atom]]]
    params: Tuple[ParamSpec, ...] = ()
    expansion: float = 1.0
    packed_outputs: Tuple[int, ...] = ()


@dataclass(frozen=True)
class CodecSpec:
    name: str
    codec_id: int  # wire-stable; never reuse
    encode: EncodeFn
    decode: DecodeFn
    n_inputs: int = 1  # -1 => variadic
    n_outputs: int = 1  # -1 => variadic (actual count recorded per node on wire)
    min_version: int = 1  # first format version that understands this codec
    doc: str = ""
    sig: Optional[CodecSig] = None  # stream-type signature (coverage-enforced)

    def run_encode(self, streams: Sequence[Stream], params: Optional[dict] = None):
        params = dict(params or {})
        if self.n_inputs >= 0 and len(streams) != self.n_inputs:
            raise ValueError(
                f"codec {self.name}: expected {self.n_inputs} inputs, got {len(streams)}"
            )
        outs, header = self.encode(list(streams), params)
        if self.n_outputs >= 0 and len(outs) != self.n_outputs:
            raise AssertionError(
                f"codec {self.name}: produced {len(outs)} outputs, spec says {self.n_outputs}"
            )
        if not isinstance(header, (bytes, bytearray)):
            raise AssertionError(f"codec {self.name}: header must be bytes")
        return [o.validate() for o in outs], bytes(header)

    def run_decode(self, out_streams: Sequence[Stream], header: bytes):
        ins = self.decode(list(out_streams), header)
        return [s.validate() for s in ins]


_BY_NAME: Dict[str, CodecSpec] = {}
_BY_ID: Dict[int, CodecSpec] = {}


def register_codec(spec: CodecSpec) -> CodecSpec:
    if spec.name in _BY_NAME:
        raise ValueError(f"duplicate codec name {spec.name!r}")
    if spec.codec_id in _BY_ID:
        raise ValueError(
            f"duplicate codec id {spec.codec_id} ({spec.name!r} vs"
            f" {_BY_ID[spec.codec_id].name!r})"
        )
    _BY_NAME[spec.name] = spec
    _BY_ID[spec.codec_id] = spec
    return spec


def get_codec(name: str) -> CodecSpec:
    _ensure_standard_library()
    try:
        return _BY_NAME[name]
    except KeyError:
        raise KeyError(f"unknown codec {name!r}; known: {sorted(_BY_NAME)}") from None


def get_codec_by_id(codec_id: int) -> CodecSpec:
    _ensure_standard_library()
    try:
        return _BY_ID[codec_id]
    except KeyError:
        raise KeyError(f"unknown codec id {codec_id}") from None


def all_codecs() -> Dict[str, CodecSpec]:
    _ensure_standard_library()
    return dict(_BY_NAME)


# ----------------------------------------------------------------- backends
HOST_BACKEND = "host"

ApplyFn = Callable[[Sequence[Stream], dict], bool]


@dataclass(frozen=True)
class BackendCodecImpl:
    """An alternate encoder for (backend, codec) — e.g. a Pallas kernel."""

    backend: str
    codec_name: str
    encode: EncodeFn
    applies: ApplyFn  # routability predicate over concrete (streams, params)


_BACKEND_IMPLS: Dict[Tuple[str, str], BackendCodecImpl] = {}


def register_backend_codec(
    backend: str,
    codec_name: str,
    encode: EncodeFn,
    applies: Optional[ApplyFn] = None,
) -> BackendCodecImpl:
    if backend == HOST_BACKEND:
        raise ValueError("'host' is the codec's own encoder; register others")
    key = (backend, codec_name)
    if key in _BACKEND_IMPLS:
        raise ValueError(f"duplicate backend impl {backend}:{codec_name}")
    impl = BackendCodecImpl(backend, codec_name, encode, applies or (lambda s, p: True))
    _BACKEND_IMPLS[key] = impl
    return impl


def get_backend_codec(backend: str, codec_name: str) -> Optional[BackendCodecImpl]:
    _ensure_standard_library()
    return _BACKEND_IMPLS.get((backend, codec_name))


def available_backends() -> List[str]:
    """'host' plus every backend with at least one registered encoder."""
    _ensure_standard_library()
    return [HOST_BACKEND] + sorted({b for b, _ in _BACKEND_IMPLS})


def run_encode_via(
    spec: CodecSpec,
    backend: str,
    streams: Sequence[Stream],
    params: Optional[dict] = None,
) -> Tuple[List[Stream], bytes, str]:
    """Encode through ``backend`` when an applicable impl exists, else host.

    Returns ``(outs, header, encoded_by)``: ``encoded_by`` names the backend
    that actually ran the node (``"host"`` when the node was routed back), so
    callers can count what ran where.  Backend output passes the same
    postconditions as the host encoder.  The encode runs inside the profiler
    span ``ozl.encode.<backend>.<codec>`` of the backend that ran it.
    """
    params = dict(params or {})
    if backend != HOST_BACKEND:
        impl = get_backend_codec(backend, spec.name)
        if impl is not None and impl.applies(streams, params):
            # injectable device-kernel failure (repro.reliability): surfaces
            # exactly where a real kernel crash would, so the session-level
            # host failover sees the same thing either way
            fault_point(f"device.encode.{backend}.{spec.name}")
            with span(f"ozl.encode.{backend}.{spec.name}"):
                outs, header = impl.encode(list(streams), params)
            if spec.n_outputs >= 0 and len(outs) != spec.n_outputs:
                raise AssertionError(
                    f"backend {backend}:{spec.name}: produced {len(outs)} outputs,"
                    f" spec says {spec.n_outputs}"
                )
            if not isinstance(header, (bytes, bytearray)):
                raise AssertionError(f"backend {backend}:{spec.name}: header must be bytes")
            return [o.validate() for o in outs], bytes(header), backend
    with span(f"ozl.encode.{HOST_BACKEND}.{spec.name}"):
        outs, header = spec.run_encode(streams, params)
    return outs, header, HOST_BACKEND


_loaded = False
_load_lock = threading.RLock()


def _ensure_standard_library() -> None:
    """Lazily import the standard codec suite so `core` has no import cycle.

    Thread-safe: the loaded flag is only set after the import completes (a
    fresh process decoding a multi-chunk container hits this from the decode
    thread pool, all threads at once).
    """
    global _loaded
    if not _loaded:
        with _load_lock:
            if not _loaded:
                from repro import codecs as _  # noqa: F401  (registers on import)

                _loaded = True
