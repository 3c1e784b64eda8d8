"""``mix`` with a closed loop whose sizes come in the draw's own order.

``mix.Schedule`` shuffles a closed loop's 256 sizes by ``--seed``.  With
fixed-size answers (``decode-closed``) that moves nothing.  With chat
lengths it chooses the work: which long prompts meet in the prefill queue,
and how many of the window's requests are long.  On four chips, five seeds
of ``mistral-7b-tp4.chat-closed`` spread ``ttft_mean_ms`` by 31%,
``itl_p95_ms`` by 5.5% and ``tok_s_chip`` by 3.1% of the median (my chip
run, PR 27, call 2): six to ten times what the open-loop cells, whose
trace is one fixed draw, show (PERF.md section 6, finding 3), and more than
any bound admits.  So here the order is the draw's, the same in every run:
request k takes entry k of the population, cycled.  ``--seed`` still draws
every prompt's token ids (and, in the harness, the weights).  What such a
cell shows is this one order of this one draw; another is another
``population_seed``.  Everything else — the parameters read, the open loop,
``prompt_ids`` — is ``mix``'s, which this module loads from beside itself.
"""

from __future__ import annotations

from pathlib import Path

from cellbench import spec

mix = spec.load_module(Path(__file__).resolve().parents[2], "generators", "mix")

prompt_ids = mix.prompt_ids


class Schedule(mix.Schedule):
    def __init__(self, traffic: dict, seed: int, seconds: float,
                 vocab_size: int):
        super().__init__(traffic, seed, seconds, vocab_size)
        if self.loop == "closed":
            self.sizes = mix.draw_sizes(traffic, mix.CLOSED_POPULATION)
