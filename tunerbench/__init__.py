"""On-chip benchmark of the streaming tuner (``repro.serve.tuning``).

``python3 tunerbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON result line.  Everything that defines the
measurement lives in this directory: the trace model and deployment
generator, the traffic mixes, the plain numpy reference and the
comparison that decides ``correct``, the trace reduction, the work
counts of the kernels and the table of peaks.  Importing this package
imports neither JAX nor the program.
"""
