"""Tokens generated per second: every new token of every request served
in the window, over the whole window on the harness clock (it ends when
the last request started inside it returns)."""


def read(run):
    if not run.requests:
        return None
    tokens = sum(r["batch"] * r["new_tokens"] for r in run.requests)
    return tokens / run.window_s
