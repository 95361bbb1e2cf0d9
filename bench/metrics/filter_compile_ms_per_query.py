"""Host milliseconds in the plan layer's ``filter-compile`` spans (a
filtered request's predicate turned into its pass mask and regime on a
plan-cache miss, ``plan/planner.py`` over ``filter/``) per filtered query
of the measured window, from the engine's ``filter_compile_s`` counter.
None where the engine has no such counter or served no filtered query."""


def read(record):
    stats = record["engine"]
    seconds = stats.get("filter_compile_s")
    queries = stats.get("filtered_queries")
    if seconds is None or not queries:
        return None
    return seconds * 1e3 / queries
