"""The ``host_copies_per_query`` reader, on hand-made engine counts."""
from __future__ import annotations

from bench import catalog
from bench.tests.helpers import REPO


def test_host_copies_per_query_counts_copies_per_query():
    read = catalog.reader("host_copies_per_query", REPO)
    assert read({"engine": {"device_copies": 30, "device_syncs": 20,
                            "queries": 12}}) == 2.5


def test_host_copies_per_query_none_without_the_count():
    # a program without the count (as before the count was added)
    read = catalog.reader("host_copies_per_query", REPO)
    assert read({"engine": {"device_syncs": 20, "queries": 12}}) is None


def test_host_copies_per_query_none_without_queries():
    read = catalog.reader("host_copies_per_query", REPO)
    assert read({"engine": {"device_copies": 3, "queries": 0}}) is None


def test_host_copies_per_query_listed_for_every_cell():
    b = catalog.load_benchmark(REPO)
    for w in b["workloads"]:
        assert "host_copies_per_query" in catalog.per_layer(b, w["name"])
