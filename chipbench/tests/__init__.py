"""A package, so that its ``conftest.py`` does not shadow the one in
``tests/`` when pytest collects both directories in one run."""
