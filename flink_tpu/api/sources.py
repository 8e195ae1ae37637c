"""Sources — bounded and unbounded microbatch producers.

ref: the FLIP-27 split-based Source API (flink-core/.../api/connector/
source/{Source,SourceReader,SplitEnumerator}.java) and the legacy
SourceFunction. TPU-first redesign: a source yields **host numpy
microbatches** (struct-of-arrays + timestamps); splits map to generator
shards so a source can be partitioned across host runners. Checkpointing
a source = recording each split's replay position (the exactly-once
contract: replayable sources, SURVEY §8.4 item 5).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterator, List, Mapping, Optional, Sequence, Tuple

import numpy as np

Batch = Tuple[Dict[str, np.ndarray], np.ndarray]  # (data fields, timestamps)


def source_is_bounded(source: "Source") -> bool:
    """Boundedness of a source instance (ref: Boundedness.BOUNDED /
    CONTINUOUS_UNBOUNDED). The framework's sources all declare
    ``bounded`` as a property; USER-defined sources sometimes spell it
    as a plain method, which this tolerates rather than treating the
    bound method object as truthy."""
    b = source.bounded
    return bool(b() if callable(b) else b)


class Source:
    """A source produces numbered microbatches per split; position = batch
    index within the split (replay = start from a position)."""

    def declared_schema(self) -> Optional[Dict[str, str]]:
        """The record schema this source emits — field name → numpy
        dtype name — or None when it cannot be known without running
        (the plan analyzer's dataflow plane seeds schema propagation
        here; analysis/dataflow.py). Declaring is optional but a source
        with no schema makes every downstream field-reference check a
        no-op."""
        return None

    def splits(self) -> List[str]:
        return ["0"]

    def open_split(self, split: str, start_pos: int = 0) -> Iterator[Batch]:
        """Yield (data, timestamps) batches from ``start_pos`` on.
        A bounded split's iterator just ends (ref: Boundedness)."""
        raise NotImplementedError

    def position_after(self, pos: int, data, ts) -> int:
        """Replay position after consuming ONE batch that started at
        ``pos`` — positions are SOURCE-defined, not framework-defined
        (the FLIP-27 split-state principle: a Kafka-style source
        checkpoints offsets, a file source checkpoints batch indices).
        The default counts batches; offset-addressed sources
        (log.LogSource) return ``pos + rows`` instead, so a restore
        resumes mid-partition at an exact record offset."""
        return pos + 1

    @property
    def bounded(self) -> bool:
        return True


@dataclasses.dataclass
class CollectionSource(Source):
    """In-memory bounded source (ref: StreamExecutionEnvironment
    .fromCollection / fromData). Splits rows into microbatches of
    ``batch_size``."""

    data: Mapping[str, np.ndarray]
    timestamps: np.ndarray
    batch_size: int = 8192

    def declared_schema(self) -> Optional[Dict[str, str]]:
        # exact by construction: the collection IS the stream
        return {k: str(np.asarray(v).dtype) for k, v in self.data.items()}

    def open_split(self, split: str, start_pos: int = 0) -> Iterator[Batch]:
        n = len(self.timestamps)
        starts = range(start_pos * self.batch_size, n, self.batch_size)
        for s in starts:
            e = min(s + self.batch_size, n)
            yield (
                {k: np.asarray(v[s:e]) for k, v in self.data.items()},
                np.asarray(self.timestamps[s:e], dtype=np.int64),
            )


@dataclasses.dataclass
class GeneratorSource(Source):
    """Rate-unbounded generator source (ref: flink-connector-datagen
    DataGeneratorSource). ``gen(split, batch_index)`` returns a batch or
    None for end-of-split — deterministic in (split, index) so replay
    after failure reproduces the stream exactly (the replayable-source
    contract)."""

    gen: Callable[[str, int], Optional[Batch]]
    n_splits: int = 1
    is_bounded: bool = True
    # declared record schema (field → numpy dtype name); None = opaque
    # generator — downstream schema checks stay silent
    schema: Optional[Dict[str, str]] = None

    def declared_schema(self) -> Optional[Dict[str, str]]:
        return dict(self.schema) if self.schema is not None else None

    def splits(self) -> List[str]:
        return [str(i) for i in range(self.n_splits)]

    def open_split(self, split: str, start_pos: int = 0) -> Iterator[Batch]:
        i = start_pos
        while True:
            b = self.gen(split, i)
            if b is None:
                return
            yield b
            i += 1

    @property
    def bounded(self) -> bool:
        return self.is_bounded


@dataclasses.dataclass
class TextLineSource(Source):
    """Line-oriented file source (ref: flink-connector-files FileSource +
    TextLineInputFormat). Emits a single string column ``line`` (object
    dtype — host-only; a tokenize/encode map must run before any device
    op) with ingest-time timestamps."""

    path: str
    batch_size: int = 8192

    def declared_schema(self) -> Optional[Dict[str, str]]:
        return {"line": "object"}

    def open_split(self, split: str, start_pos: int = 0) -> Iterator[Batch]:
        import time

        with open(self.path, "r", encoding="utf-8") as f:
            batch: List[str] = []
            index = 0
            for line in f:
                batch.append(line.rstrip("\n"))
                if len(batch) == self.batch_size:
                    if index >= start_pos:
                        now = np.int64(time.time() * 1000)
                        yield ({"line": np.array(batch, dtype=object)},
                               np.full(len(batch), now, dtype=np.int64))
                    index += 1
                    batch = []
            if batch and index >= start_pos:
                now = np.int64(time.time() * 1000)
                yield ({"line": np.array(batch, dtype=object)},
                       np.full(len(batch), now, dtype=np.int64))
