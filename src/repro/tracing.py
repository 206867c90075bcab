"""The program's host spans, kept in memory, and the names of the round's
stages on the device.

Host spans: ``with tracing.span("round", id=r): ...`` records a `Span` while
the recorder is on (`enable`); `drain` hands the recorded spans over and
empties the list. Off, the default, `span` returns one shared no-op context
manager: no clock is read and nothing is kept. Start and end come from
`time.time_ns`, the clock a profiler trace is put on with its
``profile_start_time``, so spans and device events line up; ``cpu_ns`` is
the thread's CPU time over the span (`time.thread_time_ns`), which tells
host work from waiting. `annotate` adds ``attrs`` to the span open on
the thread: what the program chose inside it, such as the forms of the
round's local step and gate and the sites they fold
(`core.engine.SwarmEngine`, inside ``session.build``).

Device stages: `core.engine` wraps the round's four stages in
`jax.named_scope` under the names in `ROUND_SCOPES`; a language model's
layers carry the names in `LM_SCOPES` inside them (`models.transformer`),
and the site-folded DenseNet its stem convolution in `CNN_SCOPES`.
XLA keeps a scope in each instruction's
``metadata={op_name="…/swarm.gate/…"}``, in a backward pass inside the
transform's name (``transpose(jvp(lm.mlp))``); `scope_of_ops` reads it
back from a compiled module's text (``Compiled.as_text()``), so the ops
of a device trace, which are named by their HLO names, can be summed per
stage.
"""
from __future__ import annotations

import re
import threading
import time
from dataclasses import dataclass
from typing import Optional

ROUND_SCOPES = ("swarm.local_steps", "swarm.propose", "swarm.gate",
                "swarm.commit")
# a language model's parts: the Mamba-2 mixer (its scan under lm.ssd),
# attention, the MLP, and the final norm, logits and loss
LM_SCOPES = ("lm.mixer", "lm.ssd", "lm.attn", "lm.mlp", "lm.head")
# the DenseNet's parts (`models.cnn.forward_cnn_sites`): its stem convolution
CNN_SCOPES = ("cnn.stem",)


@dataclass
class Span:
    name: str
    id: Optional[int]
    parent: Optional[int]  # index of the enclosing span in the same list
    start_ns: int
    end_ns: int = 0
    cpu_ns: int = 0        # the thread's CPU time between start and end
    attrs: Optional[dict] = None  # what `annotate` added


class _Off:
    """What `span` returns while the recorder is off."""

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Timed:
    __slots__ = ("rec", "span", "cpu0")

    def __init__(self, rec: "Recorder", name: str, id: Optional[int]):
        self.rec = rec
        self.span = Span(name, id, None, 0)

    def __enter__(self):
        stack = self.rec._stack()
        self.span.parent = stack[-1] if stack else None
        with self.rec._lock:
            stack.append(len(self.rec.spans))
            self.rec.spans.append(self.span)
        self.span.start_ns = time.time_ns()
        self.cpu0 = time.thread_time_ns()
        return self.span

    def __exit__(self, *exc):
        self.span.cpu_ns = time.thread_time_ns() - self.cpu0
        self.span.end_ns = time.time_ns()
        self.rec._stack().pop()
        return False


class Recorder:
    """Spans of every thread in one list, in the order they opened; each
    thread's open spans on a stack of its own (a span's parent is the span
    open on its thread when it opened). `drain` only between spans: a span
    opened before a drain and closed after it belongs to the old list."""

    def __init__(self):
        self.on = False
        self.spans: list[Span] = []
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, id: Optional[int] = None):
        """A context manager that records ``name`` while the recorder is
        on; the shared no-op `_OFF` while it is off."""
        if not self.on:
            return _OFF
        return _Timed(self, name, id)

    def annotate(self, **attrs) -> None:
        """Add ``attrs`` to the innermost span open on this thread: what
        the program chose inside it, such as the form of a compiled step.
        Nothing while the recorder is off or no span is open."""
        stack = self._stack() if self.on else None
        if stack:
            sp = self.spans[stack[-1]]
            sp.attrs = {**(sp.attrs or {}), **attrs}

    def enable(self) -> None:
        self.on = True

    def disable(self) -> None:
        self.on = False

    def drain(self) -> list[Span]:
        """The spans recorded so far; the list starts again empty."""
        with self._lock:
            out, self.spans = self.spans, []
        return out


_recorder = Recorder()
span = _recorder.span
annotate = _recorder.annotate
enable = _recorder.enable
disable = _recorder.disable
drain = _recorder.drain

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"", re.M)


def scope_of_ops(hlo_text: str, scopes=ROUND_SCOPES) -> dict[str, str]:
    """``{HLO instruction name: scope}`` for every instruction of a
    compiled module whose ``op_name`` lies under one of ``scopes`` (the
    innermost, should they nest). Instructions of no scope, such as
    copies XLA adds, are left out."""
    named = re.compile(r"(?:^|[/(])(" + "|".join(map(re.escape, scopes))
                       + r")(?=[/)]|$)")
    out = {}
    for name, op_name in _INSTR.findall(hlo_text):
        found = named.findall(op_name)
        if found:
            out[name] = found[-1]
    return out
