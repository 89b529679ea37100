------------------------- MODULE transfer_retry -------------------------
\* The money-transfer race of the tla-rust README (README.md:222-241) in this
\* repo's N-process form, with a transfer that is RETRIED: a process that is
\* done may start over, and counts how often it did.  `tries` has no bound in
\* the spec, so the model is infinite; the cfg's `CONSTRAINT TriesBounded`
\* alone makes it finite, as the reference corpus bounds its unbounded specs
\* (SpecifyingSystems/FIFO/MCInnerFIFO.cfg `CONSTRAINT qConstraint`,
\* TLC/MCAlternatingBit.cfg `SeqConstraint`; Specifying Systems ch. 14).
\* EXTENDS transfer_scaled (same directory: the spec every other desk cell
\* checks); nothing of it is retyped or edited.
EXTENDS transfer_scaled
CONSTANT MaxTries
VARIABLE tries
varsR == <<alice, bob, money, pc, tries>>
InitR == Init /\ tries = [p \in Procs |-> 0]
Retry(p) == /\ pc[p] = "done"
            /\ pc' = [pc EXCEPT ![p] = "check"]
            /\ tries' = [tries EXCEPT ![p] = @ + 1]
            /\ UNCHANGED <<alice, bob, money>>
NextR == \E p \in Procs :
            \/ (Check(p) \/ Debit(p) \/ Credit(p)) /\ UNCHANGED tries
            \/ Retry(p)
SpecR == InitR /\ [][NextR]_varsR
TriesBounded == \A p \in Procs : tries[p] <= MaxTries
=========================================================================
