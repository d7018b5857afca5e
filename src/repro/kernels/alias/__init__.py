# The alias-table build and MH probe of the alias sampler (DESIGN.md §9).
