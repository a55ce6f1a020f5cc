"""One expectation of an existing yardstick test that a cut configuration cannot
meet, marked so and carried by a new test instead of being edited away.

``test_yardstick_manifest.py::test_configuration_file_is_what_the_program_runs``
asserts ``reduced == []`` for every configuration of the manifest: true of the
two gpt2 configurations the first benchmark had, false by construction for
``olmoe-1b-7b``, whose file lists the depth it cut (one of 16 layers).
A PR that is not a benchmark PR may not edit that file, so the one new case is
expected to fail here, and ``test_yardstick_olmoe.py`` makes the same checks
with ``reduced == ["num_hidden_layers"]``. The next benchmark PR should turn
the assertion into ``cfg["reduced"] == entry["reduced"]`` and delete this file:
the marker is strict and takes an AssertionError only, so the case fails loudly
once it passes, and any other failure of it still shows.
"""

import pytest

CUT_CONFIGURATIONS = ("olmoe-1b-7b",)


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.originalname == "test_configuration_file_is_what_the_program_runs" and any(
                f"[{name}]" in item.name for name in CUT_CONFIGURATIONS):
            item.add_marker(pytest.mark.xfail(
                reason="asserts reduced == []; this configuration lists its cut depth "
                       "(checked in test_yardstick_olmoe.py)",
                raises=AssertionError, strict=True))
