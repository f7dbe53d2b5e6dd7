"""The benchmark's tokenizer: one vocabulary piece per token id, each a single
Unicode codepoint, so text <-> token ids is exact and needs no merge rules.

ids 0..2 are ``<unk>``, ``<s>`` (BOS), ``</s>`` (EOS and chat EOS); every id
from 3 up is the single codepoint ``chr(cp_of(id))``.  The vocabulary holds no
space piece (so the program adds no dummy prefix) and no piece that is the
concatenation of two others (so the program's greedy pair merge never fires).
An ASCII character of the chat template is not a piece; the program's byte
fallback turns byte ``b`` into id ``b + 3``, and so does :func:`encode_text`.

JAX-free and stdlib only: the load generator imports it.
"""

from __future__ import annotations

import os
import struct

N_SPECIAL = 3
BOS_ID, EOS_ID = 1, 2
_FIRST_CP = 0x100                 # first piece: U+0100 (nothing in ASCII/Latin-1)
_SURROGATES = (0xD800, 0xE000)    # not encodable in UTF-8: skipped
CHATML = ("{% for message in messages %}<|im_start|>{{message.role}}\n"
          "{{message.content}}<|im_end|>\n{% endfor %}<|im_start|>assistant\n")
# what the program's chatml template puts around one user message
CHAT_HEAD = "<|im_start|>user\n"
CHAT_TAIL = "<|im_end|>\n<|im_start|>assistant\n"


def cp_of(token_id: int) -> int:
    """The codepoint of piece ``token_id`` (>= 3)."""
    cp = _FIRST_CP + token_id - N_SPECIAL
    return cp + (_SURROGATES[1] - _SURROGATES[0]) if cp >= _SURROGATES[0] else cp


def id_of(ch: str) -> int:
    """The token id the program gives character ``ch``: its piece, or the
    byte fallback for a character that is no piece (ASCII)."""
    cp = ord(ch)
    if cp < _FIRST_CP:
        if cp >= 0x80:
            raise ValueError(f"character {ch!r} is neither a piece nor ASCII")
        return cp + N_SPECIAL
    if cp >= _SURROGATES[1]:
        cp -= _SURROGATES[1] - _SURROGATES[0]
    return cp - _FIRST_CP + N_SPECIAL


def text_of(ids) -> str:
    """Text whose encoding is exactly ``ids`` (all >= 3)."""
    return "".join(chr(cp_of(i)) for i in ids)


def encode_text(text: str, endpoint: str) -> list[int]:
    """The token ids the server feeds the model for a prompt ``text`` sent to
    ``endpoint`` (``completions`` or ``chat``): BOS, then one id per
    character, the chat template's characters included."""
    if endpoint == "chat":
        text = CHAT_HEAD + text + CHAT_TAIL
    return [BOS_ID] + [id_of(c) for c in text]


def overhead(endpoint: str) -> int:
    """Prompt tokens the server adds to the text's own."""
    return 1 + (len(CHAT_HEAD) + len(CHAT_TAIL) if endpoint == "chat" else 0)


def write_tokenizer(path: str, vocab_size: int) -> None:
    """A ``.t`` file (distributed-llama tokenizer format, version 1: magic,
    header size, (key, value) i32 pairs, template, then score + length +
    bytes per piece).  Scores are 0: nothing merges."""
    pieces = [b"<unk>", b"<s>", b"</s>"] + [
        chr(cp_of(i)).encode("utf-8") for i in range(N_SPECIAL, vocab_size)]
    template = CHATML.encode()
    pairs = [(0, 1), (1, vocab_size), (2, max(map(len, pieces))), (3, BOS_ID),
             (4, EOS_ID), (6, EOS_ID), (7, len(template))]
    head = b"".join(struct.pack("<ii", k, v) for k, v in pairs)
    with open(path + ".part", "wb") as f:
        f.write(struct.pack("<ii", 0x567124, 8 + len(head)))
        f.write(head)
        f.write(template)
        for p in pieces:
            f.write(struct.pack("<fi", 0.0, len(p)))
            f.write(p)
    os.replace(path + ".part", path)
