"""The benchmark harness's CPU tests (a package, so that their module
names never clash with those of the repository's ``tests/``)."""
