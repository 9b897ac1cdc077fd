"""Share of the window the step loop spent in next(batches), which is what
run_elastic's ``input`` phase wraps: the benchmark's clock around its own
feed, since the program's bucket cannot be read at the window's edges."""


def read(r):
    rep = r["report"]
    return 100.0 * rep["input_s"] / rep["window_s"]
