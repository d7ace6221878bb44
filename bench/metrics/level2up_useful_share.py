"""Share (%) of the tests launched at levels l >= 2 that PC-stable needs:
sum over l >= 2 of sum_i d_i C(d_i - 1, l), with d_i the degrees of the
graph each level started from (rebuilt from the output), over the (row,
set, neighbour) cells the level's programs launched: the level's
``launched_tests`` where it runs degree classes, n x chunks x n_chunk x n'
bucket where it runs one level-wide plan (level_stats). bench/work.py
defines both counts. None where a level keeps neither, or no graph's
output keeps level records."""
from bench import work
from bench.outputs import level_stats


def read(run):
    needed = launched = 0
    for g in run.graphs:
        stats = level_stats(g.output)
        if stats is None:
            continue
        n = g.output.adj.shape[0]
        for st in stats:
            if st["level"] < 2:
                continue
            lt = work.launched_tests(n, st)
            if lt is None:
                return None
            launched += lt
            if lt:
                needed += work.needed_tests(g.output, st["level"])
    return 100.0 * needed / launched if launched else None
