------------------------- MODULE transfer_symmetry -------------------------
\* The money-transfer race of the tla-rust README (README.md:222-241) in this
\* repo's N-process form, with the statement TLC has for processes that run
\* the same code: `SYMMETRY Perms` in the cfg (TLC.tla:13-14 Permutations;
\* Specifying Systems 14.3.4).  EXTENDS transfer_scaled (same directory: the
\* spec every other desk cell checks); nothing of it is retyped or edited.
EXTENDS transfer_scaled, TLC

Perms == Permutations(Procs)
=============================================================================
