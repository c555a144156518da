"""Programs under test, one module per ``"program"`` that a configuration names.

The harness, the op map, the calibration, the operation counts and the tiny
CPU cells reach the model only through the cell's program module
(``bench/programs/<program>.py``), and only program modules import the
program.  A program module provides:

- ``LoopConfig`` and ``run_training``: the loop that drives the step, as
  ``repro.train`` has them;
- ``build(cfg, batch)``: a namespace with ``step_fn``, ``step`` (jitted),
  ``make_state(key, aux_key)`` (a state with ``step`` and ``params``, as
  ``repro.train.TrainState``), ``loss_key`` (the logged loss that ``correct``
  compares) and ``first_gradient(state)`` (the leaves behind the ``grad``
  reading after one step: the first gradient as the optimizer keeps it);
- ``batch_spec(cfg, traffic)``: one step's abstract batch, in the form the
  traffic's generator gives it;
- ``step_flops(cfg, n)`` and ``regularizer_flops(cfg, n)``: the operations one
  step and its regularizer require, which ``mfu`` and ``reg_flops_share`` read;
- ``faults(prog, n)``: ``build``'s result broken underneath, by name, which
  ``bench/calibrate.py`` reads on the chip and the tests on the CPU;
- ``tiny(cfg, traffic)``: the configuration and traffic cut to a size that
  the CPU runs in seconds.
"""
