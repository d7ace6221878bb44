"""Share (%) of the tests launched at levels l >= 2 that PC-stable needs:
sum over l >= 2 of sum_i d_i C(d_i - 1, l), with d_i the degrees of the
graph each level started from (rebuilt from the output), over the (row,
set, neighbour) tests the level's chunk programs launched (level_stats).
bench/work.py defines both counts."""
from bench import work


def read(run):
    needed = launched = 0
    for g in run.graphs:
        if g.output is None:
            continue
        n = g.output.adj.shape[0]
        for st in g.output.level_stats:
            if st["level"] < 2:
                continue
            lt = work.launched_tests(n, st)
            if lt is None:
                return None
            launched += lt
            if lt:
                needed += work.needed_tests(g.output, st["level"])
    return 100.0 * needed / launched if launched else None
