"""Process start to the moment the first request of the window is due."""

UNIT = "s"
SOURCE = "client"


def compute(sources):
    return sources["setup"]["setup_s"]
