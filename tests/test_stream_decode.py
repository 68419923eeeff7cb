"""``data/stream_decode.py``: a stream's deltas are ``tokenizer.decode`` of
its ids, cut at whole characters, through a window of a few ids.

Tokenizers built offline, in milliseconds: a hand-made byte-level BPE (the
256 byte symbols, a few merges, pieces that END inside a CJK character or
an emoji, specials), the same vocabulary read as plain pieces, a
``CharTokenizer`` whose alphabet holds U+FFFD itself, and the committed HF
``tokenizer.json`` behind ``HFTokenizerAdapter``."""

import os
import random

import pytest

from llm_in_practise_tpu.data import BPETokenizer, CharTokenizer
from llm_in_practise_tpu.data.bpe import _BYTE_ENCODER
from llm_in_practise_tpu.data.stream_decode import StreamDecoder

FFFD = "\ufffd"
HF_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                          "tiny_tokenizer")
SPECIALS = ("[PAD]", "[UNK]", "<|im_start|>", "<|im_end|>")


def _sym(data: bytes) -> str:
    return "".join(_BYTE_ENCODER[b] for b in data)


def _bpe(pre_tokenizer: str) -> BPETokenizer:
    """Specials, the 256 byte symbols, then pieces of several bytes: whole
    words, whole characters, and the halves and thirds a byte-level BPE
    really learns (a space glued to a character's first byte, an emoji's
    first three bytes of four)."""
    pieces = [b"the", b" stream", b"ing", "日".encode(), "é".encode(),
              " 日".encode()[:2], "日".encode()[1:], "本".encode()[:2],
              "😀".encode()[:3], "😀".encode()[:2], "😀".encode()[2:],
              b"\xbf\xbd", b"\xef"]
    vocab = {s: i for i, s in enumerate(SPECIALS)}
    for b in range(256):
        vocab.setdefault(_BYTE_ENCODER[b], len(vocab))
    for p in pieces:
        vocab.setdefault(_sym(p), len(vocab))
    return BPETokenizer(vocab, [], pre_tokenizer=pre_tokenizer,
                        special_tokens=SPECIALS, unk_token="[UNK]")


def _char() -> CharTokenizer:
    return CharTokenizer.from_text("abc 日本😀é" + FFFD)


def _hf():
    pytest.importorskip("transformers")
    from llm_in_practise_tpu.data import HFTokenizerAdapter

    return HFTokenizerAdapter.from_pretrained(HF_FIXTURE)


TOKENIZERS = {"bpe-bytelevel": lambda: _bpe("bytelevel"),
              "bpe-plain": lambda: _bpe("whitespace"),
              "char": _char, "hf": _hf}


def _streamed(tok, ids) -> list[str]:
    dec = StreamDecoder(tok)
    return [dec.push(i) for i in ids] + [dec.finish()]


def _split_character_ids(tok, rng) -> list[int]:
    """Ids that spell CJK and emoji characters a BYTE a token (two, three
    and four tokens a character) where the tokenizer has byte symbols."""
    ids = []
    for ch in rng.choices("é日本😀", k=6):
        for b in ch.encode():
            tid = tok.token_to_id(_BYTE_ENCODER[b])
            if tid is None:
                return []
            ids.append(tid)
    return ids


@pytest.mark.parametrize("name", sorted(TOKENIZERS))
def test_deltas_concatenate_to_decode(name):
    """Seeded random ids (special tokens, ids past the vocabulary where the
    tokenizer has an unknown token, bytes that never become a character)
    with characters split over two to four tokens spliced in: the deltas
    and ``finish()`` concatenate to ``decode(ids)``, and a delta holds
    U+FFFD only where ``decode(ids)`` has one at that place."""
    tok = TOKENIZERS[name]()
    v = int(tok.vocab_size)
    past = 3 if isinstance(tok, BPETokenizer) else 0   # unknown ids
    for seed in range(60):
        rng = random.Random(seed)
        ids = []
        for _ in range(rng.randrange(1, 6)):
            ids += [rng.randrange(v + past)
                    for _ in range(rng.randrange(0, 40))]
            if hasattr(tok, "token_to_id"):
                ids += _split_character_ids(tok, rng)
        want = tok.decode(ids)
        deltas = _streamed(tok, ids)
        assert "".join(deltas) == want, (name, seed)
        at = 0
        for d in deltas:
            # the same characters at the same place, U+FFFD among them
            assert want[at:at + len(d)] == d
            at += len(d)
        # nothing but the last delta may END on an open character
        assert not any(d.endswith(FFFD) for d in deltas[:-1])


def test_split_characters_come_out_once_and_whole():
    tok = _bpe("bytelevel")
    text = "the 日本 streaming 😀é"
    ids = [tok.token_to_id(_BYTE_ENCODER[b]) for b in text.encode()]
    deltas = _streamed(tok, ids)
    assert "".join(deltas) == text == tok.decode(ids)
    assert [d for d in deltas if d] == list(text)
    # a token that closes no character sends nothing
    assert sum(1 for d in deltas[:-1] if not d) == len(ids) - len(text)
    # pieces that end inside the next character
    pieces = [" 日".encode()[:2], "日".encode()[1:], "😀".encode()[:3],
              "😀".encode()[3:], b"the"]
    ids = [tok.token_to_id(_sym(p)) for p in pieces]
    assert _streamed(tok, ids) == ["", " 日", "", "😀", "the", ""]


def test_special_tokens_are_skipped_and_finish_flushes_an_open_tail():
    tok = _bpe("bytelevel")
    im_end = tok.token_to_id("<|im_end|>")
    the = tok.token_to_id("the")
    open_emoji = tok.token_to_id(_sym("😀".encode()[:3]))
    assert _streamed(tok, [im_end, the, im_end, im_end]) == [
        "", "the", "", "", ""]
    # the stream ends inside a character: decode's own rendering, at the end
    deltas = _streamed(tok, [the, open_emoji])
    assert deltas == ["the", "", FFFD]
    assert "".join(deltas) == tok.decode([the, open_emoji])
    dec = StreamDecoder(tok)
    assert dec.finish() == "" and dec.finish() == ""


class _Counting:
    def __init__(self, tok):
        self.tok, self.ids, self.calls = tok, 0, 0

    def decode(self, ids):
        self.ids += len(ids)
        self.calls += 1
        return self.tok.decode(ids)


def test_ids_decoded_a_stream_are_bounded():
    """A count, not a time: over a 512-token stream the ids handed to
    ``decode`` are a handful a token (the whole-list form hands it
    1 + 2 + … + 512 = 131,328), the same in the stream's last hundred
    tokens as in its first."""
    tok = _bpe("bytelevel")
    rng = random.Random(0)
    words = [tok.token_to_id(w) for w in ("the", _sym(b" stream"), "ing")]
    ids = []
    while len(ids) < 512:
        ids += rng.choices(words, k=3)
        ids += [tok.token_to_id(_BYTE_ENCODER[b])
                for b in rng.choice("日本😀é").encode()]
    ids = ids[:512]
    counting = _Counting(tok)
    dec = StreamDecoder(counting)
    out, seen = [], []
    for i in ids:
        out.append(dec.push(i))
        seen.append(counting.ids)
    out.append(dec.finish())
    assert "".join(out) == tok.decode(ids)
    assert counting.ids <= 16 * 512
    assert counting.calls <= 2 * 512 + 1
    first, last = seen[99], seen[511] - seen[411]
    assert abs(first - last) <= 0.25 * first, (first, last)
