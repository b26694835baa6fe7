"""The traffic generator: a mix file of parameters in, requests out.

A mix names its ``kind``, and ``kinds/<kind>.py`` turns the mix's
parameters into requests with its ``generate(mix, vocab, max_active,
seed) -> Traffic``; a new arrival process is a new file there.  What a
kind may hand the window:

* ``warm``: prompts a steady node holds in its prefix cache at window
  start; set-up prefills them.
* ``in_flight``: requests already decoding at window start; set-up admits
  them together.
* ``timed``: ``(due_s, request)`` submitted at its due time whatever the
  server does (open loop).
* ``backlog``: requests that keep the queue full (offline).

Every size and every arrival comes from the mix's own ``sizes_seed``, so
each run seed serves the same requests at the same times, with the same
lengths, and the work does not change with the seed; the run seed makes
every token (and the weights, elsewhere).  The length statistics follow
``repro.training.data`` (``WorkloadGen``, ``CODING``, ``_zipf_choice``),
copied here so that the yardstick stays fixed.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

KINDS = Path(__file__).resolve().parent / "kinds"


@dataclass
class Request:
    rid: int
    tokens: list
    max_new: int


@dataclass
class Traffic:
    warm: list = field(default_factory=list)       # prompts (token lists)
    in_flight: list = field(default_factory=list)  # Requests
    timed: list = field(default_factory=list)      # (due_s, Request)
    backlog: list = field(default_factory=list)    # Requests, in order


def zipf_choice(rng, n: int, a: float, size=None):
    w = 1.0 / np.power(np.arange(1, n + 1), a)
    return rng.choice(n, size=size, p=w / w.sum())


def tokens(rng, vocab: int, n) -> list:
    return rng.integers(1, vocab, int(n)).tolist()


def kind(name: str):
    """The ``generate`` function of ``kinds/<name>.py``."""
    path = KINDS / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"unknown traffic kind {name!r}: no {path.name} "
                         f"in {KINDS.name}/")
    spec = importlib.util.spec_from_file_location(
        f"bench_kind_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate


def generate(mix: dict, vocab: int, max_active: int, seed: int) -> Traffic:
    return kind(mix["kind"])(mix, vocab, max_active, seed)
