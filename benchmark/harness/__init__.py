"""The benchmark's yardstick: traffic, plumbing, reference, reductions.

Nothing here imports ``esslivedata_tpu``: the system under test is a
child process reached through its documented entry, its topics and its
``/metrics``.
"""
