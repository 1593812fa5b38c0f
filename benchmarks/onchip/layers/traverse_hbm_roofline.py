"""Kernels: the traversal's share of the HBM roofline, in %.

Bytes the algorithm needs, whatever implements the hop: a float32
vector per distance computed and a row of the adjacency per node
expanded, summed over the real lanes of every dispatch in the traced
part of the window.  Time: the device time of the search programs
(``jit__search_*``) there.  Read against the chip's HBM bandwidth from
peaks.json."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    secs = sum(v for k, v in t["module_s"].items()
               if k.startswith("jit__search_"))
    ndists = ctx.lanes("ndists", traced=True)
    hops = ctx.lanes("hops", traced=True)
    if not secs or ndists is None or not ndists.size:
        return None
    dim, degree = ctx.config["spec"]["dim"], ctx.config["spec"]["degree"]
    nbytes = int(ndists.sum()) * dim * 4 + int(hops.sum()) * degree * 4
    return 100.0 * nbytes / secs / ctx.peaks["hbm_bytes_per_s"]
