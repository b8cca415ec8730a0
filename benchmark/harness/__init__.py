"""The benchmark's own yardstick: cell loading, model build, traffic
generation, the window, trace reduction, peaks, operation/byte arithmetic, the
plain reference and the comparison that decides `correct`.

Nothing here is imported by `nanorlhf_tpu`; from the program the benchmark
takes only the system under test (RLTrainer, ServingEngine, ServingGateway),
its phase rows, counters and kernel names.
"""
