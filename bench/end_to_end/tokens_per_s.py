"""Output tokens in content frames received inside the window / window seconds.
Under the bias one character is one token, so a frame's tokens are its
characters. Frames of requests begun in the ramp count too: the rate is all the
work of the window over all its time."""

UNIT = "tokens/s"
SOURCE = "client"


def compute(sources):
    chars = sum(r.chars_in_window for r in sources["all_requests"])
    return chars / sources["window"]["seconds"] if chars else None
