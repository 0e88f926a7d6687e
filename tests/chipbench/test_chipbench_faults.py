"""The rest of a run with the timed path broken underneath: ``correct`` has
to come out false for each fault a serving cell can have, a token or an
answer altered where it is produced."""

from __future__ import annotations

import pytest

from test_chipbench_run import CELLS, rehearse

ALTERED_TOKEN = """
from pathway_tpu.serving import generation
_init = generation.GenerationScheduler.__init__
def init(self, *a, **kw):
    _init(self, *a, **kw)
    step, calls = self._decode_fn, [0]
    def altered(*args):
        tok, lg, kp, vp = step(*args)
        calls[0] += 1
        return (tok + (calls[0] % 3 == 0)) % self.cfg.vocab_size, lg, kp, vp
    self._decode_fn = altered
generation.GenerationScheduler.__init__ = init
"""

ALTERED_ANSWER = """
from pathway_tpu.ops import topk
_search = topk.topk_search_cached
def altered(matrix, queries, k, metric, **kw):
    idx, vals = _search(matrix, queries, k, metric, **kw)
    idx = idx.copy(); idx[:, 0] = (idx[:, -1] + 97) % matrix.shape[0]
    return idx, vals
topk.topk_search_cached = altered
"""



@pytest.mark.parametrize("fault,number", [
    (ALTERED_TOKEN, "logit_gap"), (ALTERED_ANSWER, "rank_gap"),
], ids=["token_altered", "answer_altered"])
def test_broken_timed_path_is_not_correct(fault, number):
    line = rehearse(CELLS[0], seed=11, fault=fault)
    assert line["correct"] is False
    assert line["checks"][number]["value"] > line["checks"][number]["limit"]
