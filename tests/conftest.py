"""Test-wide config. NOTE: no XLA_FLAGS here — smoke tests and benches see
the real single CPU device; a test that needs several devices starts a
subprocess with its own flags."""
import warnings

warnings.filterwarnings("ignore", category=DeprecationWarning)
