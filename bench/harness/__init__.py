"""The benchmark's harness: everything a run needs besides the program.

``spec`` finds cells, configurations, traffic mixes and metric readers
by name; ``traffic`` turns a mix and a seed into requests; ``weights``
makes a configuration's weights from the seed; ``serve`` drives the
program's ``Engine`` through a measured window; ``trace`` reduces a
profiler trace; ``work`` counts the operations and bytes a step needs;
``check`` decides ``correct`` against the plain reference under
``bench/reference``.
"""
