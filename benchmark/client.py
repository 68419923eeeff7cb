"""The benchmark's HTTP client: one streaming chat request, timed on the
client's clock (``time.monotonic``), nothing read from the engine.

The server sends one SSE ``data:`` event when the first token exists (the
role chunk), one per token that decodes to text, and one carrying the
``finish_reason``. The client keeps the arrival time of each.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import time


@dataclasses.dataclass
class Outcome:
    index: int
    prompt_tokens: int
    asked_tokens: int
    t_due: float                 # open loop: scheduled; closed: == t_sent
    t_sent: float = 0.0
    t_first: float | None = None     # first SSE event
    t_done: float | None = None      # the event with the finish_reason
    token_times: list = dataclasses.field(default_factory=list)
    finish_reason: str | None = None
    status: int | None = None
    error: str | None = None

    @property
    def ok(self) -> bool:
        return (self.status == 200 and self.error is None
                and self.finish_reason in ("length", "stop"))

    @property
    def tokens(self) -> int:
        """Tokens the server generated for this request: a request that
        ran to its budget got exactly that; one that met EOS early is
        counted with the token events that arrived."""
        if self.finish_reason == "length":
            return self.asked_tokens
        return len(self.token_times)

    def ttft_s(self) -> float | None:
        return None if self.t_first is None else self.t_first - self.t_due

    def tpot_s(self) -> float | None:
        n = self.tokens
        if not self.ok or n < 2 or self.t_first is None:
            return None
        last = self.token_times[-1] if self.token_times else self.t_done
        return (max(last, self.t_first) - self.t_first) / (n - 1)


def stream_chat(port: int, model: str, content: str, out: Outcome,
                deadline: float) -> Outcome:
    """POST one streaming chat completion and fill ``out``. Gives up at
    ``deadline`` (monotonic): what has not arrived by then is missing."""
    body = json.dumps({
        "model": model, "stream": True, "temperature": 0.0,
        "max_tokens": out.asked_tokens,
        "messages": [{"role": "user", "content": content}]})
    out.t_sent = time.monotonic()
    conn = http.client.HTTPConnection(
        "127.0.0.1", port, timeout=max(deadline - out.t_sent, 0.05))
    try:
        conn.request("POST", "/v1/chat/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out.status = resp.status
        if resp.status != 200:
            out.error = resp.read(300).decode("utf-8", "replace")
            return out
        last = None
        for line in resp:
            if not line.startswith(b"data:"):
                continue
            now = time.monotonic()
            if now > deadline:
                out.error = "deadline"
                return out
            if out.t_first is None:
                out.t_first = now
            if b'"content"' in line:
                out.token_times.append(now)
            elif b"[DONE]" in line:
                break
            else:
                last = (now, line)
        if last is not None:
            event = json.loads(last[1][5:])
            if "error" in event:
                out.error = str(event["error"])[:300]
            else:
                out.finish_reason = event["choices"][0]["finish_reason"]
                out.t_done = last[0]
    except (OSError, http.client.HTTPException, ValueError) as e:
        out.error = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return out
