"""Span readers added after ``test_spans.EXPECTED`` was written bring their hand-computed values in a module and a
fixture of their own. After collection, so that ``test_spans``'s own cases stay on its own fixture, the values of
those listed in ``BENCHMARK.json`` are handed to its guard ``test_every_new_metric_has_an_expected_value``."""


def pytest_collection_modifyitems(items):
    from chipbench.tests import test_spans, test_spans_decode

    test_spans.EXPECTED.update((k, v) for k, v in test_spans_decode.EXPECTED.items() if k in test_spans.NEW)
