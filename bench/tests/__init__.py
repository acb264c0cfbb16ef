"""The benchmark's self-checks (see ``conftest.py``).

``syc-36q-m20`` runs its CPU copy on the same small Sycamore-recipe
circuit as ``syc-30q-m16`` (``bench/tests/data/tiny-syc.json``)."""

from bench.tests import _tiny

_tiny.TINY.setdefault("syc-36q-m20", "bench/tests/data/tiny-syc.json")
