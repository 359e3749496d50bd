"""A synthetic tokenizer in which every id of the vocabulary is one printable
word, written as the ``tokenizer.json`` + ``tokenizer_config.json`` that the
program's ``HFTokenizer`` loads from a model directory.

Why: over random weights the program's byte tokenizer decodes ids above 255
to "" and its engine only sends a frame when there is text, so a stream
arrives as ONE frame and a client cannot time the first token or the gaps
(PERF.md, PR 21).  Here every id decodes to text, so every token is a frame
or part of one; a prompt of N words encodes to exactly N ids; and the ids the
model emitted can be read back from the streamed text for the reference.

Words are fixed-width (``WORD_LEN`` characters: an upper-case letter, then
lower-case letters or digits), so that text streamed back WITHOUT separators
("AbcAbd") splits into the same ids as text written with spaces ("Abc Abd"):
a session's next turn can carry the model's own reply and still hit the
prefix cache.  There are no special tokens: no BOS is added to a prompt and
no id ends a generation early (``eos_id`` resolves to -1 in the program's
``HFTokenizer``), so output lengths are exactly what the traffic asked for.
"""

from __future__ import annotations

import json
from pathlib import Path

WORD_LEN = 3
_FIRST = "ABCDEFGHIJKLMNOPQRSTUVWXYZ"
_REST = "abcdefghijklmnopqrstuvwxyz0123456789"
MAX_VOCAB = len(_FIRST) * len(_REST) ** (WORD_LEN - 1)  # 33,696


def word(token_id: int) -> str:
    """The word of one id."""
    if not 0 <= token_id < MAX_VOCAB:
        raise ValueError(f"id {token_id} outside the synthetic vocabulary")
    hi, lo = divmod(token_id, len(_REST))
    first, mid = divmod(hi, len(_REST))
    return _FIRST[first] + _REST[mid] + _REST[lo]


def token_id(w: str) -> int:
    """The id of one word (inverse of :func:`word`)."""
    if len(w) != WORD_LEN:
        raise ValueError(f"not a vocabulary word: {w!r}")
    try:
        return ((_FIRST.index(w[0]) * len(_REST) + _REST.index(w[1]))
                * len(_REST) + _REST.index(w[2]))
    except ValueError:
        raise ValueError(f"not a vocabulary word: {w!r}") from None


def text_of(ids) -> str:
    """Prompt text of a list of ids: words joined by single spaces."""
    return " ".join(word(int(i)) for i in ids)


def ids_of(text: str) -> list[int]:
    """The ids of streamed or prompt text: spaces are dropped, then the text
    is cut every ``WORD_LEN`` characters."""
    s = text.replace(" ", "")
    if len(s) % WORD_LEN:
        raise ValueError(f"text of {len(s)} characters is not a whole "
                         f"number of {WORD_LEN}-character words")
    return [token_id(s[i:i + WORD_LEN]) for i in range(0, len(s), WORD_LEN)]


def write_tokenizer(model_dir: Path, vocab_size: int) -> None:
    """Write ``tokenizer.json`` and ``tokenizer_config.json`` into
    ``model_dir`` for a vocabulary of ``vocab_size`` words."""
    if vocab_size > MAX_VOCAB:
        raise ValueError(
            f"vocab {vocab_size} exceeds the {MAX_VOCAB} words of "
            f"{WORD_LEN} characters; raise WORD_LEN in a new module")
    vocab = {word(i): i for i in range(vocab_size)}
    pattern = {"Regex": f"[A-Z][a-z0-9]{{{WORD_LEN - 1}}}"}
    doc = {
        "version": "1.0",
        "truncation": None,
        "padding": None,
        "added_tokens": [],
        "normalizer": None,
        "pre_tokenizer": {
            "type": "Sequence",
            "pretokenizers": [
                {"type": "WhitespaceSplit"},
                {"type": "Split", "pattern": pattern,
                 "behavior": "Isolated", "invert": False},
            ],
        },
        "post_processor": None,
        "decoder": None,
        # An unknown word is a fault of the traffic generator, never data:
        # no unk_token in the vocabulary makes the encode raise.
        "model": {"type": "WordLevel", "vocab": vocab, "unk_token": "<unk>"},
    }
    model_dir.mkdir(parents=True, exist_ok=True)
    (model_dir / "tokenizer.json").write_text(json.dumps(doc))
    (model_dir / "tokenizer_config.json").write_text(json.dumps({
        "tokenizer_class": "PreTrainedTokenizerFast",
        "clean_up_tokenization_spaces": False,
        "model_max_length": 1 << 30,
    }))
