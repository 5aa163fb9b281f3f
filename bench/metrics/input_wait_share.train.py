"""Share of the training window the harness's loop spent blocked taking
the next batch from the program's ``PrefetchIterator`` (its
``bench.data_next`` span, host clock), in percent."""


def read(ctx):
    if "input_wait_s" not in ctx:
        return None
    return 100.0 * ctx["input_wait_s"] / ctx["window_s"]
