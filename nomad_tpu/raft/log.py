"""Raft log entries and storage (reference hashicorp/raft log +
boltdb log store; in-memory here, with the same term/index invariants).
"""

from __future__ import annotations

import threading
from typing import List, Optional, Tuple


class Entry:
    """One log entry. `wire` is the command as the text of its log line
    (`DurableLog.encode_command`), where some server has made it: the
    leader's log writer encodes a proposal once, replication ships that
    text, a follower writes it to its own log as it came, and `command`
    decodes it on first use, which is the apply thread's, off the path
    of the acknowledgement."""

    __slots__ = ("index", "term", "_command", "wire")

    def __init__(self, index: int, term: int, command: tuple = None,
                 wire: Optional[str] = None):
        self.index = index
        self.term = term
        self._command = command   # (op, args, kwargs) — see fsm.py
        self.wire = wire

    @property
    def command(self) -> tuple:
        command = self._command
        if command is None and self.wire is not None:
            import json

            from ..structs.wire import wire_decode

            # two threads may both decode: they get equal commands and
            # either may stay
            command = self._command = tuple(wire_decode(json.loads(
                self.wire)))
        return command

    def is_config(self) -> bool:
        """A membership entry? Answered from the text where that is all
        this server has, without decoding it."""
        if self._command is None and self.wire is not None:
            return self.wire.startswith('["config"')
        return tuple(self._command)[:1] == ("config",)

    def __eq__(self, other) -> bool:
        return (isinstance(other, Entry) and self.index == other.index
                and self.term == other.term
                and self.command == other.command)

    def __repr__(self) -> str:
        return (f"Entry(index={self.index}, term={self.term}, "
                f"command={self.command!r})")


class RaftLog:
    """1-indexed append-only log guarded by a lock."""

    def __init__(self):
        self._entries: List[Entry] = []
        self._lock = threading.Lock()

    def last(self) -> Tuple[int, int]:
        """-> (last_index, last_term)."""
        with self._lock:
            if not self._entries:
                return 0, 0
            e = self._entries[-1]
            return e.index, e.term

    def term_at(self, index: int) -> int:
        if index == 0:
            return 0
        with self._lock:
            if index > len(self._entries):
                return -1
            return self._entries[index - 1].term

    def get(self, index: int) -> Optional[Entry]:
        with self._lock:
            if 1 <= index <= len(self._entries):
                return self._entries[index - 1]
            return None

    def slice_from(self, index: int, limit: int = 64) -> List[Entry]:
        with self._lock:
            return list(self._entries[index - 1: index - 1 + limit])

    def append(self, term: int, command: tuple) -> Entry:
        with self._lock:
            e = Entry(index=len(self._entries) + 1, term=term, command=command)
            self._entries.append(e)
            return e

    def append_batch(self, term: int, commands: List[tuple],
                     prev: Optional[Tuple[int, int]] = None,
                     encoded: Optional[List[str]] = None
                     ) -> Optional[List[Entry]]:
        """Append a whole batch in one lock hold (the group-commit
        primitive; DurableLog adds the single-fsync disk write on top).

        When ``prev`` is given the append is conditional on the tail
        still being exactly ``(last_index, last_term)``: the log writer
        snapshots the tail under the node lock, builds the batch outside
        it, and any interleaved append — a config entry, a new leader's
        noop, a follower truncation after step-down — fails the
        compare-and-swap instead of landing the batch on a diverged log.
        Returns None on a CAS mismatch. ``encoded`` (the commands' log
        text, DurableLog's to write) is unused: this log keeps no bytes."""
        with self._lock:
            if not self._entries:
                tail = (0, 0)
            else:
                e = self._entries[-1]
                tail = (e.index, e.term)
            if prev is not None and tail != tuple(prev):
                return None
            batch = [Entry(index=tail[0] + 1 + i, term=term, command=c)
                     for i, c in enumerate(commands)]
            self._entries.extend(batch)
            return batch

    def append_entries(self, prev_index: int, entries: List[Entry]) -> bool:
        """Follower-side: truncate conflicts after prev_index, then
        append (the AppendEntries receiver rules). Returns True when a
        conflicting suffix was truncated (membership must be
        recomputed — a dropped entry may have been a config change)."""
        truncated = False
        with self._lock:
            for e in entries:
                pos = e.index - 1
                if pos < len(self._entries):
                    if self._entries[pos].term != e.term:
                        del self._entries[pos:]
                        self._entries.append(e)
                        truncated = True
                    # else: already have it
                else:
                    self._entries.append(e)
        return truncated

    def length(self) -> int:
        with self._lock:
            return len(self._entries)
