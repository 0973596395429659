"""Sharded executor: how unevenly the window's ops fell on the mesh's
shards, from the program's per-shard routing counter
``rtpu_shard_ops{shard}``: S x the largest shard's ops over all shards'
ops, with S the cell's chips (one shard a chip).  1.0 when the shards are
even, S when one chip takes everything; the slowest shard sets a launch's
time.  None on a program without the family or a window that routed no
op through a partition."""

from benchmark.metrics._program import _family


def snapshot(sut):
    fam = _family(sut, "rtpu_shard_ops_total")
    if fam is None:
        return {}
    return {labels[0]: c.value for labels, c in fam.items()}


def read(before, after, run):
    d = [after[s] - before.get(s, 0) for s in after]
    n = sum(d)
    return run["chips"] * max(d) / n if n > 0 else None
