------------------------ MODULE transfer_violation ------------------------
\* The money-transfer race of the tla-rust README (README.md:222-241) with
\* the two properties the race BREAKS, beside the one it keeps.  EXTENDS
\* transfer_scaled (same directory: the N-process form every other cell of
\* the benchmark checks); nothing of it is retyped or edited.
\*
\* AliceSolvent is the README's `C: assert alice_account >= 0`, whose
\* violation ends TLC's run there (README.md:265-321): first false at
\* distance 4 (check, check, debit, debit).  NoMoneyCreated is the README's
\* `MoneyInvariant == alice_account + bob_account = account_total` read as a
\* STATE invariant of conservation: bob never holds more money than existed.
\* First false at distance 6 (check, check, debit, debit, credit, credit):
\* the deeper of the two, and the one bench/specs/transfer_violation_4p.cfg
\* checks.
EXTENDS transfer_scaled

AliceSolvent == alice >= 0

NoMoneyCreated == bob <= MaxMoney
=============================================================================
