"""The engine's own seconds, as each request's terminal message gives them
(``seconds``): the mean per request in milliseconds, or the sum per pass
in seconds."""


def read(w, per):
    secs = [r.done["seconds"] for r in w.requests
            if r.done is not None and "seconds" in r.done]
    if not secs:
        return None
    if per == "pass":
        return sum(secs) / w.passes if w.passes else None
    return 1000.0 * sum(secs) / len(secs)
