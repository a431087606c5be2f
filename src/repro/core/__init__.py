"""repro.core — the graph model of compression (paper §III).

Public API:
    Stream types ............ repro.core.message  (serial/numeric/struct/strings)
    Codec registry .......... repro.core.codec
    Graph authoring ......... repro.core.graph    (GraphBuilder, Plan, pipeline)
    Selectors ............... repro.core.selector
    Engine .................. repro.core.engine   (compress / decompress / Compressor)
    Wire format ............. repro.core.wire
    Serialized compressors .. repro.core.serialize
    Format versioning ....... repro.core.versioning
"""
from .message import Stream, SType, serial, numeric, struct, strings  # noqa: F401
from .graph import GraphBuilder, Plan, PlanNode, pipeline  # noqa: F401
from .codec import (  # noqa: F401
    CodecSpec,
    register_codec,
    get_codec,
    all_codecs,
    register_backend_codec,
    get_backend_codec,
    available_backends,
)
from .selector import SelectorSpec, register_selector, get_selector  # noqa: F401
from .engine import (  # noqa: F401
    CompressionCtx,
    Compressor,
    CompressorSession,
    DecompressorSession,
    ExecScratch,
    ResolvedPlan,
    ResolvedStep,
    SessionPool,
    StreamMeta,
    compress,
    decompress,
    decompress_bytes,
    execute,
    resolve,
    resolve_cache_clear,
    resolve_cache_info,
    set_resolve_check,
    stream_meta,
)
from .versioning import (  # noqa: F401
    CONTAINER_MIN_VERSION,
    CURRENT_FORMAT_VERSION,
    MIN_FORMAT_VERSION,
    VersionError,
)
