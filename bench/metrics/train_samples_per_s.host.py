"""``train_samples_per_s`` of the host-fed cell, a metric of its own: its
runs spread as the host's NumPy does, which the device-fed cells' bound
must not take up (PERF.md)."""
from bench import manifest

read = manifest.load_metric("train_samples_per_s").read
