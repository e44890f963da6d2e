"""Share of the decode window spent inside the fleet executors: the sum
of ``GemmRecord.exec_time`` (the program's host span around each fleet
GEMM, synced on its output) over the records of the window's decode
steps."""


def read(ctx):
    if ctx["kind"] != "decode" or not ctx["records"]:
        return None
    return 100.0 * sum(r.exec_time for r in ctx["records"]) / ctx["window_s"]
