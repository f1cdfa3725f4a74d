"""Test-only families (``bench.families.PACKAGE`` points here in a test)."""
