"""Output tokens produced in the window over the window's seconds."""


def read(run):
    if not run.window_tokens_s:
        return None
    return run.window_tokens / run.window_tokens_s
