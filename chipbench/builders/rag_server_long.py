"""Builder ``rag_server_long``: builder ``rag_server_kinds`` for answers
whose prompts are longer than one prefill program.

Two things differ.  The answering model is given the cache a slot holds as
the configuration states it (``serving.max_cache``): ``JaxChat``'s own
default, 1,024 tokens, would keep a 2.8 k-token prompt's last 960.  And
warm-up first walks the scheduler through every prefill program a prompt
of the mix's lengths reaches: such a prompt takes several programs, the
last of them at one of three widths by what is left of it, each at the
block-table width its pages so far bucket to, and one answer's prompt
reaches only some of them.  The scheduler names the programs a length
runs (``prefill_programs``); a token prompt of the first length that
reaches each program not yet met is generated once (one token each), so
whatever program the window's answers run has run before it.  Settling
stays the last step.
"""

from __future__ import annotations

import sys

from chipbench import text
from chipbench.builders import rag_server_kinds


class LongPromptRagServer(rag_server_kinds.SettledRagServer):
    def __init__(self, config: dict, seed: int, work_dir: str):
        super().__init__(config, seed, work_dir)
        self.server.rag.llm.max_cache = self.serving["max_cache"]

    def warm_up(self, mix: dict) -> None:
        sched = self.scheduler()
        # a longer prompt is cut to its last tokens, as an answer's is
        kept = sched.max_cache - self.serving["max_new_tokens"]
        lo, hi = (min(n, kept) for n in self.prompt_lengths(mix))
        lengths = lengths_to_warm(sched.prefill_programs, lo, hi)
        futures = [sched.submit_ids(self._ids(n), max_new_tokens=1) for n in lengths]
        for f in futures:
            f.result(timeout=900)
        print(
            f"chipbench: prompts of {lo}-{hi} tokens: lengths {lengths} warmed",
            file=sys.stderr, flush=True,
        )
        super().warm_up(mix)

    def prompt_lengths(self, mix: dict) -> tuple[int, int]:
        """The shortest and the longest prompt the mix can send: the
        fewest and the most words a question has, with the ``search_topk``
        shortest and longest documents, through the decoder's tokenizer."""
        decoder = self.spec.get("decoder") or self.config
        tokenizer = text.HashTokenizer(decoder["vocab_size"])
        k = self.serving["search_topk"]
        docs = sorted(self.documents, key=lambda d: len(tokenizer.encode(d, 8192)))
        lo, hi = mix["payload"]["words"]
        ends = [
            len(tokenizer.encode(text.rag_prompt(chosen, question), 8192))
            for chosen, question in (
                (docs[:k], text.make_questions(1, 0, (lo, lo))[0]),
                (docs[-k:], text.make_questions(1, 0, (hi, hi))[0]),
            )
        ]
        return min(ends), max(ends)

    def _ids(self, n: int) -> list[int]:
        vocab = (self.spec.get("decoder") or self.config)["vocab_size"]
        return [104 + i % (vocab - 104) for i in range(n)]


def lengths_to_warm(programs_of, lo: int, hi: int) -> list[int]:
    """Of the lengths ``lo`` to ``hi``, the shortest to reach each prefill
    program that ``programs_of(length)`` names: together they run every
    program a prompt of those lengths does."""
    met: set = set()
    lengths = []
    for n in range(lo, hi + 1):
        programs = set(programs_of(n))
        if not programs <= met:
            met |= programs
            lengths.append(n)
    return lengths


def build(config: dict, seed: int, work_dir: str) -> LongPromptRagServer:
    """After the same look as ``rag_server_kinds``: a program that does not
    read the ``model_type`` fails here, before anything is built."""
    from pathway_tpu.models.decoder import decoder_config_from_hf

    described = decoder_config_from_hf({k: v for k, v in config.items() if k != "chipbench"})
    if described.runs is None:
        raise RuntimeError(
            f"model_type {config.get('model_type')!r} was read as a model whose layers are all alike"
        )
    return LongPromptRagServer(config, seed, work_dir)
