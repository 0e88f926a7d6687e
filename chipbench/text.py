"""Seeded text for the cells, and the two plain copies the checks need of
what the deployment does to text: the hashing tokenizer that stands in
where the image holds no vocabulary, and the RAG prompt.

Nothing here imports the program.  ``tests/chipbench`` pins both copies
to the program's own at the tiny size.
"""

from __future__ import annotations

import hashlib
import random
import re

WORDS = (
    "stream table index shard epoch commit window join reduce filter key "
    "value batch device kernel page cache token vector query answer chunk "
    "source sink schema column row delta snapshot replay worker mesh chip "
    "memory bandwidth latency throughput embed retrieve rank prompt decode "
    "ledger tenant quota replica leader follower lease fence barrier drain "
    "ingest parse split merge route admit shed retry timeout deadline trace"
).split()

_WORD = re.compile(r"\w+|[^\w\s]")
CLS_ID, SEP_ID, PAD_ID = 101, 102, 0
# ids the hashing tokenizer's decode leaves out of a response's text
DROPPED_IDS = (CLS_ID, SEP_ID, PAD_ID)


def spread(lo: int, hi: int, n: int, rng: random.Random) -> list[int]:
    """``n`` whole numbers spread evenly over ``[lo, hi]``, in an order
    drawn from ``rng``: every seed gets the same set of sizes."""
    sizes = [lo + (i * (hi - lo + 1)) // n for i in range(n)] if n else []
    rng.shuffle(sizes)
    return sizes


def make_documents(n: int, seed: int, words: tuple[int, int]) -> list[str]:
    """``n`` distinct documents ``document <i> : <words>``; their lengths
    are the same set for every seed, their words and order are not."""
    rng = random.Random(seed * 1_000_003 + 17)
    sizes = spread(words[0], words[1], n, rng)
    return [
        f"document {i} : " + " ".join(rng.choice(WORDS) for _ in range(size))
        for i, size in enumerate(sizes)
    ]


def make_questions(
    n: int, seed: int, words: tuple[int, int], documents: list[str] | None = None
) -> list[str]:
    """``n`` questions of ``words`` words.  With ``documents`` each one
    quotes a run of words of a seeded document, so that retrieval has
    something to find; without, its words are drawn from the vocabulary."""
    rng = random.Random(seed * 1_000_003 + 29)
    sizes = spread(words[0], words[1], n, rng)
    out = []
    for i, size in enumerate(sizes):
        if documents:
            body = rng.choice(documents).split(" : ", 1)[1].split()
            start = rng.randrange(max(1, len(body) - size + 1))
            picked = body[start:start + size]
            picked += [rng.choice(WORDS) for _ in range(size - len(picked))]
        else:
            picked = [rng.choice(WORDS) for _ in range(size)]
        # the ordinal keeps two requests of one run from being equal rows
        out.append(f"q{i} " + " ".join(picked[: size - 1]))
    return out


class HashTokenizer:
    """Words and punctuation hashed into the vocabulary (ids 0..103 are
    kept free as BERT's special range); ``[CLS] ... [SEP]`` around them."""

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size

    def encode(self, text: str, max_length: int) -> list[int]:
        ids = [CLS_ID]
        for tok in _WORD.findall(text or "")[: max_length - 2]:
            h = int.from_bytes(
                hashlib.blake2b(tok.lower().encode(), digest_size=4).digest(), "little"
            )
            ids.append(104 + h % (self.vocab_size - 104))
        ids.append(SEP_ID)
        return ids


def parse_served_tokens(response: str) -> list[int]:
    """Token ids out of a response of the hashing tokenizer's decode
    (``tok12 tok7 ...``)."""
    return [int(w[3:]) for w in response.split()]


def rag_prompt(context_texts: list[str], question: str) -> str:
    """The text the decoder is given for a question and its retrieved
    documents: the deployment's default QA template, sent as one user
    message."""
    return (
        "user: Please provide an answer based solely on the provided sources. "
        "When referencing information from a source, cite it. "
        "If none of the sources are helpful, respond with: No information found. "
        "\nSources:\n" + "\n\n".join(context_texts)
        + f"\nQuestion: {question}\nAnswer:"
    )
