"""Process start to window open: import, native codec load, data
generation, cluster start, warm-up and its compiles."""


def read(ctx):
    return ctx["setup_s"]
