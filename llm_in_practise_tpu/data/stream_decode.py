"""Incremental detokenisation for a token stream.

A streaming endpoint sends a request's text as its tokens arrive. Decoding
the whole list at every token costs O(n) an event and O(n²) a stream, all
of it under the GIL beside the engine thread. :class:`StreamDecoder` hands
``tokenizer.decode`` a short window instead, by the two-offset scheme the
serving stacks use (vLLM's ``detokenize_incrementally``, TGI's
``decode_token``): ``prefix`` and ``read`` index the stream, the window is
``ids[prefix:]``, and what a token adds is ``decode(ids[prefix:])`` past
``decode(ids[prefix:read])``. The ids before ``read`` are in the window
only as context (a tokenizer may decode a piece differently after another
piece than alone); once the window's text ends on a whole character both
offsets move up.

The contract: ``"".join(push(i) for i in ids) + finish()`` is exactly
``tokenizer.decode(ids)``. A character whose bytes arrive in several tokens
comes out once, whole, with the token that completes it: while the window's
text ends in U+FFFD (what a decoder puts for bytes that are not yet a
character) nothing is emitted and the offsets stay, so the window is as
long as the run of tokens that end inside a character, or in bytes that
never become one (``finish`` hands those over as ``decode`` renders them).
One algorithm through ``decode`` alone, whatever the tokenizer.
"""

from __future__ import annotations

_INCOMPLETE = "\ufffd"


class StreamDecoder:
    """``push(token_id) -> str`` a token, ``finish() -> str`` at the end."""

    def __init__(self, tokenizer):
        self._decode = tokenizer.decode
        self._ids: list[int] = []   # ids[prefix:], the window
        self._read = 0              # read - prefix: context ids in the window
        self._sent = 0              # len(decode(ids[prefix:read])), all sent

    def push(self, token_id: int) -> str:
        """The text this token completes ("" while a character is open, or
        for a token ``decode`` skips)."""
        ids = self._ids
        ids.append(token_id)
        text = self._decode(ids)
        if text.endswith(_INCOMPLETE):
            return ""
        delta = text[self._sent:]
        del ids[:self._read]
        self._read = len(ids)
        self._sent = len(self._decode(ids))
        return delta

    def finish(self) -> str:
        """What the stream still holds back: text that ends in U+FFFD,
        as ``decode`` renders it."""
        delta = self._decode(self._ids)[self._sent:]
        self._sent += len(delta)    # a second finish() has nothing left
        return delta
