------------------------- MODULE transfer_scaled -------------------------
\* Scalable benchmark workload for jaxmc: the README money-transfer race
\* (/root/reference/README.md:222-241) generalized to N processes and a
\* configurable money domain, written directly in TLA+ so the state-space
\* size is cfg-tunable. Safety: alice only ever decreases (AliceBounded),
\* which holds despite the race. This is the round-1 flagship bench spec
\* (raft.tla is the round-2+ target, SURVEY.md §6).
EXTENDS Naturals

CONSTANTS Procs, MaxMoney

VARIABLES alice, bob, money, pc

vars == <<alice, bob, money, pc>>

Init == /\ alice = MaxMoney
        /\ bob = 0
        /\ money \in [Procs -> 1..MaxMoney]
        /\ pc = [p \in Procs |-> "check"]

Check(p) == /\ pc[p] = "check"
            /\ pc' = [pc EXCEPT ![p] =
                         IF alice >= money[p] THEN "debit" ELSE "done"]
            /\ UNCHANGED <<alice, bob, money>>

Debit(p) == /\ pc[p] = "debit"
            /\ alice' = alice - money[p]
            /\ pc' = [pc EXCEPT ![p] = "credit"]
            /\ UNCHANGED <<bob, money>>

Credit(p) == /\ pc[p] = "credit"
             /\ bob' = bob + money[p]
             /\ pc' = [pc EXCEPT ![p] = "done"]
             /\ UNCHANGED <<alice, money>>

Terminating == /\ \A p \in Procs : pc[p] = "done"
               /\ UNCHANGED vars

Next == (\E p \in Procs : Check(p) \/ Debit(p) \/ Credit(p)) \/ Terminating

Spec == Init /\ [][Next]_vars

AliceBounded == alice <= MaxMoney
=============================================================================
