"""(row, set, neighbour) cells the chunk programs of levels l >= 2 launched,
per graph: the sum of the levels' ``launched_tests`` in level_stats, mean
over the window's graphs. None where a level that ran keeps no such count
(a level-wide chunk plan)."""


def read(run):
    per_graph = []
    for g in run.graphs:
        if g.output is None:
            continue
        cells = 0
        for st in g.output.level_stats:
            if st["level"] < 2 or st.get("skipped"):
                continue
            if "launched_tests" not in st:
                return None
            cells += st["launched_tests"]
        per_graph.append(cells)
    return sum(per_graph) / len(per_graph) if per_graph else None
