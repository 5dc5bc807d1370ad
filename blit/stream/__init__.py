"""``blit.stream`` — the streaming ingest plane (ISSUE 7): reduce while
the telescope records.

Everything upstream of here assumes GUPPI RAW at rest; this package
feeds the SAME reducers from sources still being written — a growing
file the recorder appends to, a paced replay, an in-memory queue — with
watermark-based windowing, late/duplicate/missing-chunk repair (missing
chunks mask to zero weight, the PR 2 antenna discipline), and bounded
chunk→product latency as a first-class metric.  ``blit stream`` is the
CLI.

The golden contract: streaming a fully-recorded file through
:func:`stream_reduce` / :func:`stream_search` produces BYTE-IDENTICAL
``.fil``/``.h5``/``.hits`` products to the batch path.
"""

from blit.stream.cursor import StreamCursor
from blit.stream.packet import (
    PacketAssembler,
    PacketFramer,
    PacketReplaySource,
    PacketSource,
    packets_of,
)
from blit.stream.plane import LiveRawStream, stream_reduce, stream_search
from blit.stream.session import SessionSupervisor, source_from_spec
from blit.stream.source import (
    ChunkSource,
    FileTailSource,
    QueueSource,
    ReplaySource,
    StreamChunk,
    chunks_of,
)

__all__ = [
    "ChunkSource",
    "FileTailSource",
    "LiveRawStream",
    "PacketAssembler",
    "PacketFramer",
    "PacketReplaySource",
    "PacketSource",
    "QueueSource",
    "ReplaySource",
    "SessionSupervisor",
    "StreamChunk",
    "StreamCursor",
    "chunks_of",
    "packets_of",
    "source_from_spec",
    "stream_reduce",
    "stream_search",
]
