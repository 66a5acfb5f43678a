"""Plain references, one module per architecture. A configuration file
names its module (``"reference": "<module>"`` selects
``chipbench/refs/<module>.py``), so a new architecture's cell is added with
new files only. A module imports nothing of the program under test, takes
the numerics every reference shares from ``chipbench.reference`` and the
work counts from ``chipbench.work``, and has:

``arch(sizes) -> arch``
    What the forward needs, read from the configuration's own keys
    (``harness.sizes(config, smoke)``). Hashable: jitted functions take it
    as a static argument.
``leaves(arch) -> {path: (shape, dtype, init)}``
    Every weight, under the program's parameter-tree paths, each with the
    ``weights.make`` init (None: the default normal draw). The harness
    checks these paths, shapes and dtypes against the program's parameter
    tree before any weight is made, and makes the program's weights from
    them.
``program_fields(sizes) -> {attribute: value}``
    The program's ``ModelConfig`` attributes the configuration fixes; the
    harness refuses a program config that differs on any of them.
``gaps(arch, draw, prompt, served, quant=None) -> np.ndarray``
    For each served token of one request, how far its logit lies below the
    reference's best (``chipbench.reference.gaps``); with ``quant``, the
    control's reading. ``draw(names)`` returns the named leaves, made from
    the run's seed; it keeps the last set it returned, so asking for the
    same set again costs nothing, and a module that asks for a few leaves
    at a time holds only those.
``padded_len(n) -> int``
    The sequence length a forward of ``n`` tokens runs at: what the harness
    counts against the reference's budget when it draws the sample.
``model_ops(arch, *, prompt_lens=(), decode_contexts=()) -> int``
    Least operations of prefilling prompts of ``prompt_lens`` tokens and
    decoding one token at each context in ``decode_contexts`` (the ``mfu``
    metric's numerator).
"""
