"""Builder ``rag_server_kinds``: builder ``rag_server``, for a decoder whose
layers are of several kinds, after one look that the program reads the
configuration's ``model_type`` as such a model, and with one more step of
warm-up that leaves the server in the same regime in every run.

A program from before layer kinds would read a ``mimo_v2`` ``config.json``
as a llama-shaped model of those sizes and serve it for a whole run; here
it fails at once instead (it has no ``decoder_config_from_hf``).

**The regime** (PERF.md section 6, PR 29; ROADMAP S8b).  Every request is a
commit of its own and so an epoch of its own, except that two answers that
wait together behind a third share the next epoch while the engine's clock
is ahead of their route's: ``internals/runner.py`` moves an epoch whose time
is not past the last one's to ``last_time + 2`` and folds into it every row
staged up to there.  The engine's clock is ahead by one for every epoch
another route caused (set-up's ``/v1/statistics``, ``/v1/retrieve``,
``/v1/pw_ai_answer``, ``/v1/pw_list_documents``, the corpus, idle drains),
and every shared epoch uses one up: a fresh process shares its first 0 to 6
such epochs, as set-up happened to leave it, and none after that.  At this
cell's rate the window holds about five pairs that wait together, so the
runs of one program fell into modes (`answer_p90_ms` 1.15 s where four
pairs shared, 1.46 s where none did).  ``settle`` sends answers three at a
time until three that were sent together come back one epoch apart: the
lead is used up, the server is where a process is that has answered for a
while, and the window meets one regime from its first request to its last.
"""

from __future__ import annotations

import sys
import threading
import time

from chipbench import text
from chipbench.builders import common, rag_server

# each probe uses up one or two of the lead; set-up leaves 0 to 6
SETTLE_PROBES = 32


class SettledRagServer(rag_server.RagServer):
    def warm_up(self, mix: dict) -> None:
        super().warm_up(mix)
        lo, _hi = mix["payload"]["words"]
        question = text.make_questions(1, 0, (lo, lo))[0]
        probes = self.settle(question)
        print(
            f"chipbench: answers that wait together stopped sharing an epoch after {probes} probes",
            file=sys.stderr, flush=True,
        )

    def settle(self, question: str) -> int | None:
        """Probes sent until one came back an epoch apart; ``None`` where
        waiting answers share an epoch however many are sent (a program
        with no such lead to use up is in one regime as it is)."""
        for probe in range(1, SETTLE_PROBES + 1):
            if self._comes_back_apart(question):
                return probe
        return None

    def _comes_back_apart(self, question: str) -> bool:
        """Three answers sent together: the first to arrive is served, the
        other two wait behind it.  Answers of one epoch are delivered in
        the same instant; those of epochs in a row one answer's time apart."""
        done: list[float] = []

        def ask() -> None:
            common.post(self.port, "/v2/answer", {"prompt": question})
            done.append(time.monotonic())

        threads = [threading.Thread(target=ask) for _ in range(3)]
        sent = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if len(done) != 3:
            raise RuntimeError("a settling answer failed")
        done.sort()
        one_answer = done[0] - sent
        return min(done[1] - done[0], done[2] - done[1]) > 0.5 * one_answer


def build(config: dict, seed: int, work_dir: str) -> rag_server.RagServer:
    from pathway_tpu.models.decoder import decoder_config_from_hf

    described = decoder_config_from_hf({k: v for k, v in config.items() if k != "chipbench"})
    if described.runs is None:
        raise RuntimeError(
            f"model_type {config.get('model_type')!r} was read as a model whose layers are all alike"
        )
    return SettledRagServer(config, seed, work_dir)
