# The paper's primary contribution: the three-component synthetic graph
# generation framework (structure / features / aligner) plus the chunked
# trillion-edge generation machinery.  Import the submodules directly
# (``repro.core.pipeline``, ``repro.core.structure``, ...): this package
# re-exports nothing, so ``repro.kernels`` can import ``repro.core.descend``
# without loading the sampler, which imports the kernels.
