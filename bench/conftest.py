"""The benchmark's tests run on the CPU's virtual devices, like the
repo's own (tests/conftest.py): a pytest worker may collect only these
files, so the flag is set here too, before anything imports JAX."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
