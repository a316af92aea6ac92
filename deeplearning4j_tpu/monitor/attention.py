"""Which path each attention site of a traced train step, or of a
paged decode program, took.

``scaled_dot_product_attention`` (ops/nn_ops.py) computes attention
tile by tile where its inputs and the program's place allow it, and
with a whole score matrix everywhere else. Which of the two a model
got is decided while its step is TRACED, so it shows in no span and no
runtime counter: this record is where it shows. The step builder
(``SameDiff._build_step_parts``) opens one :class:`AttentionSites` each
time a train step is traced, tells it how many devices the model's
arrays span, and hands it to the op through
``ops.nn_ops.attention_trace_scope``; the op calls :meth:`note` once a
site. The newest record is ``sd.attention_sites`` on the model and
:func:`last_train_step` here, for a report, ``chip_smoke.py`` or a test
to read without a profiler trace.

A paged decode program that can read its pages in place through a kernel
(``zoo.cohere2_moe``, ``zoo.paged_attend.kernel_refusal``) keeps the same
record, one site a layer, opened each time such a program is traced:
:func:`last_decode_program`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class AttentionSites:
    """The attention sites of ONE traced program (a train step, or a
    paged decode program).

    ``devices`` is what the tracer of the step knows and the op cannot
    see from its arrays: how many devices the program is traced for.
    ``first_reason`` names why the first plain site was plain."""
    devices: int = 1
    kernel: int = 0
    plain: int = 0
    first_reason: Optional[str] = None

    def note(self, reason: Optional[str]) -> None:
        """One site: ``reason`` None took the tiled kernel, anything
        else is why it took the plain path."""
        if reason is None:
            self.kernel += 1
            return
        self.plain += 1
        if self.first_reason is None:
            self.first_reason = reason

    def counts(self) -> tuple:
        """``(kernel sites, plain sites, first reason)``."""
        return self.kernel, self.plain, self.first_reason

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


_LAST: Optional[AttentionSites] = None
_LAST_DECODE: Optional[AttentionSites] = None


def open_train_step(devices: int) -> AttentionSites:
    """A fresh record for a train step that is about to be traced; it
    becomes :func:`last_train_step`."""
    global _LAST
    _LAST = AttentionSites(devices=int(devices))
    return _LAST


def last_train_step() -> Optional[AttentionSites]:
    """The record of the train step traced last in this process (None
    before any)."""
    return _LAST


def open_decode_program() -> AttentionSites:
    """A fresh record for a paged decode program that is about to be
    traced; it becomes :func:`last_decode_program`."""
    global _LAST_DECODE
    _LAST_DECODE = AttentionSites()
    return _LAST_DECODE


def last_decode_program() -> Optional[AttentionSites]:
    """The record of the paged decode program traced last in this
    process (None before any): ``counts()`` is (layers that read their
    pages through the kernel, layers that gathered the table, why the
    first of those did)."""
    return _LAST_DECODE


__all__ = ["AttentionSites", "open_train_step", "last_train_step",
           "open_decode_program", "last_decode_program"]
