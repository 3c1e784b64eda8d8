"""Traffic over shared long documents: a fixed set of documents, each asked
again and again with a short question of its own, by callers that wait for
the answer.  Same interface as ``mix.py`` (``Schedule``, ``request(k)``,
``sizes``, ``sampling``, ``prompt_ids``); pure standard library — the load
generator's process imports it and must never import jax.

A prompt is a document followed by a question.  The documents' token ids
come from ``--seed`` and are fixed for the run; request k asks document
k mod D (so the first D requests are the D first asks) with a question whose
ids are the request's own.  Question and answer lengths are one draw from the
traffic file's ``population_seed``, in the draw's own order: every seed offers
the same work (mix.py says why the seed must not reorder it).

Document lengths are multiples of ``DOC_UNIT`` tokens and questions shorter
than it, so a prompt's length says what it is made of:
``prompt_len // DOC_UNIT * DOC_UNIT`` tokens of document, the rest question.
``prompt_ids`` reads a length that way for the harness's warm-up prompts
(negative index, one for every prefill shape of ``sizes``): they are real
documents with a question, so that warming a shape also makes the document
resident, as it is in the steady state the window measures.  Warm-up prompt i
of a length takes document ``i mod WARM_DOCS`` of that length.  A prompt
shorter than ``DOC_UNIT`` (the check's) has ids of its own, like mix.py's.

Traffic file fields read here:

``loop``            ``"closed"`` only
``clients``         number of callers
``documents``       ``{"lengths": [...], "each": n}``: n documents of every
                    length; document d has length ``lengths[d mod len]``
``question_len`` / ``output_len``   as mix.py's length distributions
``sampling``        fields copied into each request body
``ramp_s``          seconds of load before the window opens: long enough for
                    every document's first ask to have been prefilled
``population_seed`` seed of the draw of question and answer lengths
"""

from __future__ import annotations

import random
from functools import lru_cache
from pathlib import Path

from cellbench import spec

# the request record and the length distributions are mix.py's
mix = spec.load_module(Path(__file__).resolve().parents[2], "generators", "mix")
Request, _draw_len = mix.Request, mix._draw_len

DOC_UNIT = 8192         # document lengths are multiples, questions shorter
WARM_DOCS = 4           # documents of one length that warm-up prompts name
POPULATION = 240        # (question, answer) lengths a run cycles through


@lru_cache(maxsize=64)
def document(seed: int, length: int, ordinal: int, vocab_size: int) -> tuple:
    """Document ``ordinal`` of ``length`` tokens.  Ids 1..vocab-1: 0 is the
    tokenizer's unknown."""
    rng = random.Random((seed * 1_000_003 + length) * 1_000_003 + ordinal)
    return tuple(rng.randrange(1, vocab_size) for _ in range(length))


def question(seed: int, index: int, n: int, vocab_size: int) -> list[int]:
    """Request ``index``'s question: ids of its own."""
    rng = random.Random(seed * 1_000_003 + index)
    return [rng.randrange(1, vocab_size) for _ in range(n)]


def prompt_ids(seed: int, index: int, n: int, vocab_size: int) -> list[int]:
    """A prompt of n tokens outside the schedule (warm-up and check prompts
    have a negative index): a document and a question where n says so."""
    length = n // DOC_UNIT * DOC_UNIT
    if not length:
        return question(seed, index, n, vocab_size)
    doc = document(seed, length, (-index - 1) % WARM_DOCS, vocab_size)
    return list(doc) + question(seed, index, n - length, vocab_size)


class Schedule:
    """What the load generator sends: an endless closed-loop sequence.
    ``sizes`` lists every (prompt, answer) length that can occur."""

    def __init__(self, traffic: dict, seed: int, seconds: float,
                 vocab_size: int):
        if traffic["loop"] != "closed":
            raise ValueError("shared_docs plays a closed loop only")
        self.loop, self.dues, self.n = "closed", None, None
        self.clients = int(traffic["clients"])
        self.ramp_s = float(traffic.get("ramp_s", 0.0))
        self.seed, self.vocab_size = seed, vocab_size
        self.sampling = dict(traffic.get("sampling", {}))
        lengths = [int(n) for n in traffic["documents"]["lengths"]]
        each = int(traffic["documents"]["each"])
        q_max = int(traffic["question_len"].get(
            "max", traffic["question_len"].get("value", 0)))
        if any(n % DOC_UNIT or not n for n in lengths) or q_max >= DOC_UNIT:
            raise ValueError(
                f"document lengths must be multiples of {DOC_UNIT} and "
                "questions shorter")
        # document d: (length, ordinal among the documents of that length)
        self.documents = [(lengths[d % len(lengths)], d // len(lengths))
                          for d in range(len(lengths) * each)]
        if POPULATION % len(self.documents):
            raise ValueError(
                f"{len(self.documents)} documents do not divide the "
                f"population of {POPULATION}")
        pop = random.Random(int(traffic["population_seed"]))
        self._draw = [(_draw_len(pop, traffic["question_len"]),
                       _draw_len(pop, traffic["output_len"]))
                      for _ in range(POPULATION)]
        self.sizes = [(self.documents[j % len(self.documents)][0] + q, o)
                      for j, (q, o) in enumerate(self._draw)]

    def request(self, k: int) -> Request:
        q, o = self._draw[k % POPULATION]
        length, ordinal = self.documents[k % len(self.documents)]
        doc = document(self.seed, length, ordinal, self.vocab_size)
        return Request(
            index=k, due_s=None,
            prompt=list(doc) + question(self.seed, k, q, self.vocab_size),
            max_tokens=o, sampling=self.sampling)
