"""Share of the training window spent inside the fleet executors: the sum
of ``GemmRecord.exec_time`` (the program's host span around each fleet
GEMM, synced on its output) over the window's steps."""


def read(ctx):
    if ctx["kind"] != "train" or not ctx["records"]:
        return None
    return 100.0 * sum(r.exec_time for r in ctx["records"]) / ctx["window_s"]
